"""Capped per-worker reservation queues (the [W, R] probe encoding):

* the retired dense [J, W] sparrow path, kept here as a reference
  implementation, is reproduced BITWISE by the queue path when the cap
  and insertion window are ample;
* ``late_bind``'s prefix-sum search equals the dense [J, W]
  formulation on random inputs, holes in the pending mask included;
* the per-job prefix-sum counts (``job_counts``, ``compact_queues``)
  equal the retired [T]-wide scatters on random contiguous layouts, and
  sparrow and eagle built on the retired scatters reach the same final
  state bit for bit, with and without faults;
* eagle's per-edge SSS re-routing lands probes on exactly the dense
  rejection/re-route formula's cells;
* probe sampling is rank-based: every job probes exactly
  ``min(d * n_tasks, W)`` DISTINCT workers (the old ``scores <= kth``
  threshold could select more on tied uniforms);
* a deliberately undersized cap overflows (counted), yet completes the
  trace with parity-close delays (orphan rescue preserves liveness);
* carried state is independent of the trace length;
* the queue passes run at the queues' live width (``at_width``): the
  width helper covers the widest queue (under ``vmap``, the batch's), and
  sparrow and eagle reach the retired full-width passes' final state bit
  for bit — clean, under crash waves, with an undersized cap, across the
  widths, streamed and vmapped.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.base import Job
from repro.simx import (
    SimxConfig,
    TelemetryConfig,
    empty_schedule,
    engine,
    export_workload,
)
from repro.simx import eagle as simx_eagle
from repro.simx import runtime as simx_runtime
from repro.simx import sparrow as simx_sparrow
from repro.simx import sweep as simx_sweep
from repro.simx.state import (
    init_eagle_state,
    init_sparrow_state,
    probe_edge_layout,
)
from repro.simx.stream import run_steady_state
from repro.workload.synth import ReplayArrivals, synthetic_trace
from repro.workload.traces import Workload


# ---------------------------------------------------------------------------
# the retired dense [J, W] encoding, kept as the reference implementation
# ---------------------------------------------------------------------------


def dense_late_bind(job_pick, pend_task, job, job_start):
    """The dense [J, W] late-binding formulation the queue path replaced
    (claim mask + per-row cumsum serve ranks + a [J, W] slot table)."""
    T = job.shape[0]
    W = job_pick.shape[0]
    J = job_start.shape[0]
    t_row = jnp.arange(T, dtype=jnp.int32)
    j_col = jnp.arange(J, dtype=jnp.int32)[:, None]
    pending = jnp.zeros(J, jnp.int32).at[job].add(pend_task.astype(jnp.int32))
    claim_j = job_pick[None, :] == j_col                        # bool[J,W]
    serve_rank = jnp.cumsum(claim_j, axis=1, dtype=jnp.int32) - 1
    serve = claim_j & (serve_rank < pending[:, None])
    c = jnp.cumsum(pend_task, dtype=jnp.int32)
    base = jnp.where(job_start > 0, c[jnp.maximum(job_start - 1, 0)], 0)
    prank = c - 1 - base[job]                                   # int32[T]
    slot = jnp.full((J, W), T, jnp.int32).at[
        job, jnp.where(pend_task & (prank < W), prank, W)
    ].set(t_row, mode="drop")                                   # int32[J,W]
    srank = jnp.where(serve, serve_rank, W)
    task_pick = jnp.min(
        jnp.where(
            serve,
            jnp.take_along_axis(slot, jnp.clip(srank, 0, W - 1), axis=1),
            T,
        ),
        axis=0,
    )                                                           # int32[W]
    return jnp.any(serve, axis=0), task_pick


def scatter_late_bind(job_pick, pend_task, job, job_start):
    """The retired O(T + W log W) late binding: per-job pending counts and
    a [T] slot table, both scattered over every task."""
    T = job.shape[0]
    W = job_pick.shape[0]
    J = job_start.shape[0]
    t_row = jnp.arange(T, dtype=jnp.int32)
    w_row = jnp.arange(W, dtype=jnp.int32)
    pend_i = pend_task.astype(jnp.int32)
    pending = jnp.zeros(J, jnp.int32).at[job].add(pend_i)
    c = jnp.cumsum(pend_i, dtype=jnp.int32)
    base = jnp.where(job_start > 0, c[jnp.maximum(job_start - 1, 0)], 0)
    prank = c - 1 - base[job]
    slot = jnp.full(T, T, jnp.int32).at[
        jnp.where(pend_task, job_start[job] + prank, T)
    ].set(t_row, mode="drop")
    order = jnp.argsort(job_pick, stable=True)
    sj = job_pick[order]
    first = jnp.searchsorted(sj, sj, side="left").astype(jnp.int32)
    rank = jnp.zeros(W, jnp.int32).at[order].set(w_row - first)
    jp = jnp.clip(job_pick, 0, J - 1)
    serve = (job_pick < J) & (rank < pending[jp])
    pos = job_start[jp] + rank
    return serve, jnp.where(serve, slot[jnp.clip(pos, 0, T - 1)], T)


def scatter_compact_queues(resq, task_finish, job, t, num_jobs):
    """The retired queue compaction: unfinished counts by a [T] scatter."""
    W, R = resq.shape
    unfinished = (
        jnp.zeros(num_jobs + 1, jnp.int32)
        .at[job]
        .add((task_finish > t).astype(jnp.int32))
    )
    live = (resq < num_jobs) & (unfinished[jnp.minimum(resq, num_jobs)] > 0)
    pos = jnp.cumsum(live, axis=1) - 1
    w_rows = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[:, None], (W, R))
    out = (
        jnp.full((W, R), num_jobs, jnp.int32)
        .at[w_rows, jnp.where(live, pos, R)]
        .set(resq, mode="drop")
    )
    return out, jnp.sum(live, axis=1, dtype=jnp.int32)


def random_layout(rng, mode):
    """A random contiguous per-job layout and a per-task mask over it:
    ``(ntasks, job, job_start, job_end, mask)``.

    ``random``: iid mask.  ``repend``: each job launched a prefix of its
    tasks, then some launched tasks re-pended (fault holes: pending is
    not a suffix).  ``pad``: a streaming window — empty job slots and a
    pad job owning the trailing task slots, which never pend.  ``edge``:
    the first and last jobs fully pending, every other job's mask empty
    or full.  Every mode has jobs with zero set entries."""
    J = int(rng.integers(4, 10))
    ntasks = rng.integers(1, 12, J)
    if mode == "pad":
        ntasks[rng.random(J) < 0.3] = 0                     # empty slots
        ntasks[-1] = rng.integers(5, 20)                    # the pad job
    T = int(ntasks.sum())
    job = np.repeat(np.arange(J), ntasks).astype(np.int32)
    end = np.cumsum(ntasks).astype(np.int32)
    start = (end - ntasks).astype(np.int32)
    if mode == "random":
        mask = rng.random(T) < 0.5
    elif mode == "repend":
        launched = np.arange(T) - start[job] < rng.integers(0, ntasks + 1)[job]
        mask = ~launched | (launched & (rng.random(T) < 0.3))
    elif mode == "pad":
        mask = (rng.random(T) < 0.6) & (job < J - 1)
    else:
        mask = np.repeat(rng.random(J) < 0.5, ntasks)
        mask[job == 0] = mask[job == J - 1] = True
    mask[job == rng.integers(0, J)] = False                 # a job with none
    return ntasks, job, start, end, mask


LAYOUT_MODES = ("random", "repend", "pad", "edge")


@pytest.mark.parametrize("mode", LAYOUT_MODES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_job_counts_and_compaction_match_scatter(mode, seed):
    """Property: the per-job prefix-sum counts equal the [T]-wide scatter,
    and ``compact_queues`` equals its scatter formulation bit for bit."""
    rng = np.random.default_rng([seed, LAYOUT_MODES.index(mode)])
    ntasks, job, start, end, mask = random_layout(rng, mode)
    J = ntasks.size
    c, base, count = simx_sparrow.job_counts(
        jnp.asarray(mask), jnp.asarray(start), jnp.asarray(end)
    )
    np.testing.assert_array_equal(np.asarray(count), np.bincount(job, mask, J))
    np.testing.assert_array_equal(np.asarray(c), np.cumsum(mask))
    np.testing.assert_array_equal(
        np.asarray(base), [mask[:s].sum() for s in start]
    )
    W, R = 11, 5
    t = jnp.float32(1.0)
    task_finish = jnp.asarray(
        np.where(mask, np.inf, rng.choice([0.5, 1.0, 2.0], job.size)), jnp.float32
    )
    resq = jnp.asarray(rng.integers(0, J + 1, (W, R)), jnp.int32)
    got = simx_sparrow.compact_queues(
        resq, task_finish, t, jnp.asarray(start), jnp.asarray(end)
    )
    want = scatter_compact_queues(resq, task_finish, jnp.asarray(job), t, J)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def run_dense_sparrow(cfg, tasks, seed, num_rounds):
    """The retired fault-free dense sparrow rule: probe mask [J, W] placed
    at arrival rounds, per-round dense min-over-jobs late binding.
    Returns (task_finish, worker_finish, probes, messages)."""
    W = cfg.num_workers
    T = tasks.num_tasks
    J = tasks.num_jobs
    d = cfg.probe_ratio
    probes = simx_sparrow.probe_mask(jax.random.PRNGKey(seed), cfg, tasks)
    j_col = jnp.arange(J, dtype=jnp.int32)[:, None]
    job_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(tasks.job_ntasks, dtype=jnp.int32)[:-1]]
    )

    @jax.jit
    def step(carry):
        t, task_finish, worker_finish, probed, n_probes, messages = carry
        job_seen = tasks.job_submit <= t
        newly = job_seen & ~probed
        new_probes = jnp.sum(
            jnp.where(newly, jnp.minimum(d * tasks.job_ntasks, W), 0),
            dtype=jnp.int32,
        )
        pend_task = jnp.isinf(task_finish) & (tasks.submit <= t)
        pending = (
            jnp.zeros(J, jnp.int32).at[tasks.job].add(pend_task.astype(jnp.int32))
        )
        active = probes & (pending > 0)[:, None] & job_seen[:, None]
        job_pick = jnp.min(jnp.where(active, j_col, J), axis=0)
        idle = worker_finish <= t
        launch, task_pick = dense_late_bind(
            jnp.where(idle, job_pick, J), pend_task, tasks.job, job_start
        )
        lt = jnp.where(launch, task_pick, T)
        start = t + 3 * cfg.hop
        dur = tasks.duration[jnp.clip(task_pick, 0, T - 1)]
        task_finish = task_finish.at[lt].set(start + dur, mode="drop")
        worker_finish = jnp.where(launch, start + dur, worker_finish)
        messages = messages + new_probes + 2 * jnp.sum(launch, dtype=jnp.int32)
        return (
            t + cfg.dt, task_finish, worker_finish, probed | newly,
            n_probes + new_probes, messages,
        )

    carry = (
        jnp.float32(0.0),
        jnp.full(T, jnp.inf, jnp.float32),
        jnp.full(W, -jnp.inf, jnp.float32),
        jnp.zeros(J, jnp.bool_),
        jnp.int32(0),
        jnp.int32(0),
    )
    for _ in range(num_rounds):
        carry = step(carry)
    return carry[1], carry[2], carry[4], carry[5]


@pytest.fixture(scope="module")
def small():
    wl = synthetic_trace(num_jobs=12, tasks_per_job=24, load=0.8, num_workers=48, seed=9)
    tasks = export_workload(wl)
    return tasks


def test_queue_path_matches_dense_reference_bitwise(small):
    """The tentpole pin: with an ample cap (R = J: every job can always
    hold a reservation) and a full-width insertion window, the [W, R]
    encoding reproduces the dense path's task/worker timelines and
    probe/message counters BIT FOR BIT."""
    tasks = small
    edge_job, *_ = probe_edge_layout(
        SimxConfig(num_workers=48), tasks
    )
    cfg = SimxConfig(
        num_workers=48, dt=0.02,
        reserve_cap=tasks.num_jobs, probe_window=int(edge_job.size),
    )
    rounds = engine.estimate_rounds(cfg, tasks)
    q = simx_sparrow.simulate_fixed(cfg, tasks, 7, rounds)
    fin, wfin, probes, messages = run_dense_sparrow(cfg, tasks, 7, rounds)
    assert jnp.array_equal(q.task_finish, fin)
    assert jnp.array_equal(q.worker_finish, wfin)
    assert int(q.probes) == int(probes)
    assert int(q.messages) == int(messages)
    assert int(q.res_overflow) == 0


def test_queue_path_matches_dense_with_auto_knobs(small):
    """The *auto* cap/window (the defaults every caller gets) are sized so
    the small trace still matches the dense reference bitwise — overflow
    and window lag are reserved for genuinely pathological settings."""
    tasks = small
    cfg = SimxConfig(num_workers=48, dt=0.02)
    rounds = engine.estimate_rounds(cfg, tasks)
    q = simx_sparrow.simulate_fixed(cfg, tasks, 3, rounds)
    fin, _, probes, _ = run_dense_sparrow(cfg, tasks, 3, rounds)
    assert int(q.res_overflow) == 0
    assert int(q.probes) == int(probes)
    assert jnp.array_equal(q.task_finish, fin)


@pytest.mark.parametrize("mod", [simx_sparrow, simx_eagle])
def test_non_contiguous_layout_is_refused(small, mod):
    """The per-job prefix sums read each job's tasks as one slice: a fixed
    trace whose tasks interleave jobs is refused when the step is built."""
    tasks = small
    job = np.asarray(tasks.job).copy()
    job[[0, -1]] = job[[-1, 0]]
    mixed = dataclasses.replace(tasks, job=jnp.asarray(job))
    cfg = SimxConfig(num_workers=48, dt=0.02)
    with pytest.raises(ValueError, match="contiguously"):
        mod.simulate_fixed(cfg, mixed, 0, 1)


@pytest.mark.parametrize(
    "seed",
    [0, 1, 2, 3]
    + [
        pytest.param((mode, s), id=f"{mode}-{s}")
        for mode in ("repend", "pad", "edge")
        for s in (0, 1, 2)
    ],
)
def test_late_bind_matches_dense_reference(seed):
    """Property: the prefix-sum late_bind equals the dense [J, W]
    formulation (and the retired [T] slot scatter) on random claim
    patterns (incl. over-claimed jobs, idle workers, and jobs with zero
    pending tasks); the layout cases add re-pended holes, a streaming pad
    job and fully pending first and last jobs."""
    if isinstance(seed, tuple):
        mode, s = seed
        rng = np.random.default_rng([s, LAYOUT_MODES.index(mode)])
        ntasks, job_np, start, end, pend_np = random_layout(rng, mode)
        J, W = ntasks.size, 33
    else:
        rng = np.random.default_rng(seed)
        J, W = 7, 33
        ntasks = rng.integers(1, 9, J)
        T = int(ntasks.sum())
        job_np = np.repeat(np.arange(J), ntasks)
        end = np.cumsum(ntasks)
        start = end - ntasks
        pend_np = rng.random(T) < 0.5
    job = jnp.asarray(job_np, jnp.int32)
    job_start, job_end = jnp.asarray(start, jnp.int32), jnp.asarray(end, jnp.int32)
    pend = jnp.asarray(pend_np)
    pick = jnp.asarray(rng.integers(0, J + 1, W), jnp.int32)  # J = no claim
    counts = simx_sparrow.job_counts(pend, job_start, job_end)
    l_new, t_new = simx_sparrow.late_bind(pick, *counts)
    l_old, t_old = dense_late_bind(pick, pend, job, job_start)
    np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_old))
    np.testing.assert_array_equal(np.asarray(t_new), np.asarray(t_old))
    l_sc, t_sc = scatter_late_bind(pick, pend, job, job_start)
    np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_sc))
    np.testing.assert_array_equal(np.asarray(t_new), np.asarray(t_sc))


@pytest.fixture
def scatter_rules(monkeypatch):
    """Rebuild sparrow and eagle on the retired [T]-wide scatters: per-job
    counts scattered over ``tasks.job``, the [T] slot-table late binding
    and the scatter compaction.  The patched ``job_counts`` hands the mask
    itself through to ``late_bind`` in place of its prefix sum.  Returns
    ``install(tasks)`` and the number of patched calls."""
    calls = []

    def install(tasks):
        J = tasks.num_jobs
        end = jnp.cumsum(tasks.job_ntasks, dtype=jnp.int32)
        start = end - tasks.job_ntasks

        def job_counts(mask, job_start, job_end):
            calls.append("counts")
            count = jnp.zeros(J, jnp.int32).at[tasks.job].add(mask.astype(jnp.int32))
            return mask, None, count

        def late_bind(job_pick, mask, base, pending):
            calls.append("bind")
            return scatter_late_bind(job_pick, mask, tasks.job, start)

        def compact_queues(resq, task_finish, t, job_start, job_end):
            calls.append("compact")
            return scatter_compact_queues(resq, task_finish, tasks.job, t, J)

        for mod in (simx_sparrow, simx_eagle):
            monkeypatch.setattr(mod, "job_counts", job_counts)
            monkeypatch.setattr(mod, "late_bind", late_bind)
            monkeypatch.setattr(mod, "compact_queues", compact_queues)

    return install, calls


@pytest.mark.parametrize("faulty", [False, True], ids=["clean", "crashes"])
@pytest.mark.parametrize("mod", [simx_sparrow, simx_eagle])
def test_rule_matches_scatter_formulation_bitwise(mod, faulty, scatter_rules):
    """Rule-level pin: sparrow and eagle reach the same final state, every
    field and counter, as the same rules built on the retired [T]-wide
    scatters — with and without repeated worker crashes (re-pended holes)
    and an undersized queue cap (overflow, orphan rescue)."""
    rng = np.random.default_rng(5)
    jobs_wl = synthetic_trace(num_jobs=14, tasks_per_job=12, load=0.9, num_workers=40, seed=8)
    tasks = export_workload(jobs_wl)
    est = np.asarray(tasks.job_est).copy()
    est[::4] = 99.0                                   # eagle's long jobs
    tasks = dataclasses.replace(tasks, job_est=jnp.asarray(est))
    cfg = SimxConfig(num_workers=40, dt=0.02, reserve_cap=3, long_threshold=10.0)
    rounds = engine.estimate_rounds(cfg, tasks, slack=4.0)
    faults = None
    if faulty:
        down = np.full(40, np.inf, np.float32)
        hit = rng.permutation(40)[:20]
        down[hit] = np.linspace(0.2, 3.0, 20).astype(np.float32)
        faults = empty_schedule(40, cfg.num_gms).replace(
            worker_down=jnp.asarray(down), worker_up=jnp.asarray(down + 0.1)
        )
    new = mod.simulate_fixed(cfg, tasks, 3, rounds, faults=faults)
    install, calls = scatter_rules
    install(tasks)
    old = mod.simulate_fixed(cfg, tasks, 3, rounds, faults=faults)
    assert {"counts", "bind", "compact"} <= set(calls)
    if faulty:
        assert int(new.lost) > 0
    assert int(new.res_overflow) > 0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(old)
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("seed", [0, 5])
def test_eagle_edge_sss_matches_dense_formula(seed):
    """Per-edge SSS rejection/re-routing lands each probe on exactly the
    cell the retired dense mask formulas computed (reject -> +off1 shift
    -> second reject -> +off2 into the short partition), with identical
    rejection counts."""
    rng = np.random.default_rng(seed)
    J, W, R = 6, 40, 8
    bm = rng.random((J, W)) < 0.2                     # initial probe cells
    reject = rng.random(W) < 0.3
    off1 = rng.integers(0, W, J)
    off2 = rng.integers(0, R, J)
    # dense formulas (verbatim from the retired eagle rule)
    w_row = np.arange(W)
    rej0 = bm & reject[None, :]
    moved1 = np.take_along_axis(rej0, (w_row[None, :] - off1[:, None]) % W, axis=1)
    rej1 = moved1 & reject[None, :]
    land2 = np.zeros((J, W), bool)
    tgt2 = (w_row[None, :] + off2[:, None]) % R
    np.maximum.at(land2, (np.repeat(np.arange(J), W), tgt2.ravel()), rej1.ravel())
    dense = (bm & ~reject[None, :]) | (moved1 & ~reject[None, :]) | land2
    # per-edge equivalent (what insert_probes receives)
    ej, ew = np.nonzero(bm)
    e_rej0 = reject[ew]
    w1 = np.where(e_rej0, (ew + off1[ej]) % W, ew)
    e_rej1 = e_rej0 & reject[w1]
    wfin = np.where(e_rej1, (w1 + off2[ej]) % R, w1)
    edge_mask = np.zeros((J, W), bool)
    edge_mask[ej, wfin] = True
    np.testing.assert_array_equal(edge_mask, dense)
    assert int(e_rej0.sum()) == int(rej0.sum())
    assert int(e_rej1.sum()) == int(rej1.sum())


def test_insert_probes_merges_duplicate_reservations():
    """Dense-reference parity for eagle's SSS collisions: a probe landing
    where the same job already holds (same-round or earlier-round) a
    reservation merges into one queue entry — not a duplicate slot, not
    an overflow."""
    J = 5  # empty sentinel
    resq = jnp.full((4, 2), J, jnp.int32).at[2, 0].set(3)  # job 3 queued on w2
    fill = jnp.asarray([0, 0, 1, 0], jnp.int32)
    #           dup-pair same (job, target)   held from earlier round
    targets = jnp.asarray([1, 1, 1, 2], jnp.int32)
    jobs = jnp.asarray([0, 0, 1, 3], jnp.int32)
    ins = jnp.ones(4, bool)
    out, n_over = simx_sparrow.insert_probes(resq, fill, targets, jobs, ins)
    assert int(n_over) == 0
    w1 = sorted(int(x) for x in out[1])
    assert w1 == [0, 1]                      # merged: one entry per job
    assert [int(x) for x in out[2]] == [3, J]  # re-probe of a held job is a no-op
    # a genuinely full queue still counts overflow
    _, n_over2 = simx_sparrow.insert_probes(
        out, jnp.asarray([0, 2, 1, 0], jnp.int32),
        jnp.asarray([1], jnp.int32), jnp.asarray([4], jnp.int32),
        jnp.ones(1, bool),
    )
    assert int(n_over2) == 1


@pytest.mark.parametrize(
    "num_jobs,tasks_per_job,num_workers",
    [(20, 16, 64), (6, 40, 64), (9, 3, 7), (5, 100, 129)],
)
def test_probe_mask_rows_are_exact(num_jobs, tasks_per_job, num_workers):
    """Satellite property pin: every row of the (rank-based) probe mask
    holds exactly min(d * n_tasks, W) distinct probes — including the
    d * n > W saturation case and odd worker counts, where the old
    ``scores <= kth`` threshold mask could select extra workers on tied
    scores."""
    wl = synthetic_trace(
        num_jobs=num_jobs, tasks_per_job=tasks_per_job, load=0.5,
        num_workers=num_workers, seed=1,
    )
    tasks = export_workload(wl)
    cfg = SimxConfig(num_workers=num_workers)
    for seed in range(5):
        mask = simx_sparrow.probe_mask(jax.random.PRNGKey(seed), cfg, tasks)
        rows = np.asarray(jnp.sum(mask, axis=1))
        want = np.minimum(
            cfg.probe_ratio * np.asarray(tasks.job_ntasks), num_workers
        )
        np.testing.assert_array_equal(rows, want)


def test_eagle_probe_mask_matches_short_only_edge_layout():
    """The dense eagle reference view stays consistent with the per-edge
    layout the transition rule actually uses: long-job rows are empty and
    short rows carry exactly the short_only edge counts."""
    from repro.simx.eagle import eagle_probe_mask

    wl = synthetic_trace(num_jobs=10, tasks_per_job=8, load=0.5, num_workers=32, seed=6)
    tasks = export_workload(wl)
    # mark a third of the jobs long via the estimate threshold
    est = np.asarray(tasks.job_est).copy()
    est[::3] = 99.0
    tasks = dataclasses.replace(tasks, job_est=jnp.asarray(est))
    cfg = SimxConfig(num_workers=32, long_threshold=10.0)
    mask = np.asarray(eagle_probe_mask(jax.random.PRNGKey(3), cfg, tasks))
    _, _, edge_end, _ = probe_edge_layout(cfg, tasks, short_only=True)
    k_per_job = np.diff(np.concatenate([[0], edge_end]))
    np.testing.assert_array_equal(mask.sum(axis=1), k_per_job)
    assert (mask[::3] == False).all()  # noqa: E712 — long rows empty


def test_probe_targets_distinct_and_match_mask():
    """The queue path's target table and the dense reference mask are two
    views of one sample: rows are duplicate-free and scatter to the mask."""
    wl = synthetic_trace(num_jobs=8, tasks_per_job=12, load=0.5, num_workers=32, seed=2)
    tasks = export_workload(wl)
    cfg = SimxConfig(num_workers=32)
    key = jax.random.PRNGKey(11)
    kmax = int(min(cfg.probe_ratio * int(np.max(np.asarray(tasks.job_ntasks))), 32))
    tg = np.asarray(simx_sparrow.probe_targets(key, cfg, tasks, kmax))
    for row in tg:
        assert len(set(row.tolist())) == kmax  # distinct within each job
    mask = np.asarray(simx_sparrow.probe_mask(key, cfg, tasks))
    for j, row in enumerate(tg):
        k = min(cfg.probe_ratio * int(tasks.job_ntasks[j]), 32)
        assert mask[j, row[:k]].all()


@pytest.mark.parametrize("mod", [simx_sparrow, simx_eagle])
def test_queue_overflow_accounted_and_parity_close(mod):
    """Satellite: a deliberately undersized cap (R = 1 on an overlapping
    trace) drops probes — res_overflow > 0 — yet every task still
    completes (orphan rescue) with delays in the same regime as the
    ample-cap run."""
    wl = synthetic_trace(num_jobs=24, tasks_per_job=16, load=0.9, num_workers=32, seed=4)
    tasks = export_workload(wl)
    ample = SimxConfig(num_workers=32, dt=0.02)
    tight = dataclasses.replace(ample, reserve_cap=1)
    rounds = engine.estimate_rounds(ample, tasks, slack=8.0)
    a = mod.simulate_fixed(ample, tasks, 0, rounds)
    b = mod.simulate_fixed(tight, tasks, 0, rounds)
    assert int(a.res_overflow) == 0
    assert int(b.res_overflow) > 0
    sa = simx_sweep.point_summary(a, tasks)
    sb = simx_sweep.point_summary(b, tasks)
    assert int(sa["tasks_done"]) == int(sb["tasks_done"]) == tasks.num_tasks
    assert float(sb["p50"]) == pytest.approx(float(sa["p50"]), rel=0.5, abs=0.25)


def test_probe_window_saturation_is_counted():
    """A deliberately tiny insertion window lags behind arrivals; the
    ``probe_lag`` counter records the saturated rounds (and is surfaced
    by ``point_summary``), while an auto-sized window stays at zero and
    still inserts every probe."""
    wl = synthetic_trace(num_jobs=16, tasks_per_job=16, load=0.9, num_workers=32, seed=2)
    tasks = export_workload(wl)
    auto = SimxConfig(num_workers=32, dt=0.02)
    tiny = dataclasses.replace(auto, probe_window=4)
    rounds = engine.estimate_rounds(auto, tasks, slack=8.0)
    a = simx_sparrow.simulate_fixed(auto, tasks, 0, rounds)
    b = simx_sparrow.simulate_fixed(tiny, tasks, 0, rounds)
    assert int(a.probe_lag) == 0
    assert int(b.probe_lag) > 0
    assert int(a.probes) == int(b.probes)  # lag delays probes, never drops
    assert int(simx_sweep.point_summary(b, tasks)["probe_lag"]) > 0
    assert int(simx_sweep.point_summary(b, tasks)["tasks_done"]) == tasks.num_tasks
    # an EXACT-fit window (every probe inserted at its arrival round, no
    # ready edge left beyond it) is not lag — no false alarm
    burst = synthetic_trace(num_jobs=5, tasks_per_job=10, load=0.9,
                            num_workers=32, seed=3)
    btasks = export_workload(burst)
    bsub = jnp.zeros_like(btasks.submit)
    btasks = dataclasses.replace(
        btasks, submit=bsub, job_submit=jnp.zeros_like(btasks.job_submit)
    )
    exact = dataclasses.replace(auto, probe_window=100)  # == P = 5 * 20
    c = simx_sparrow.simulate_fixed(
        exact, btasks, 0, engine.estimate_rounds(exact, btasks, slack=8.0)
    )
    assert int(c.probe_lag) == 0 and int(c.probes) == 100


def test_carried_state_independent_of_trace_length():
    """Acceptance: the scan-carried probe state is [W, R] with R capped,
    so it cannot grow with the job count — and paper-scale J-heavy grid
    points clear the default memory guard."""
    cfg = SimxConfig(num_workers=64, reserve_cap=8)
    shapes = []
    for j in (10, 200):
        wl = synthetic_trace(num_jobs=j, tasks_per_job=8, load=0.5,
                             num_workers=64, seed=1)
        tasks = export_workload(wl)
        shapes.append(init_sparrow_state(cfg, tasks).resq.shape)
        assert init_eagle_state(cfg, tasks).resq.shape == (64, 8)
    assert shapes[0] == shapes[1] == (64, 8)
    # the auto cap saturates at 64 slots no matter how long the trace is
    auto = SimxConfig(num_workers=64)
    assert auto.queue_cap(10**9) == 64
    # 2000 jobs x 50k workers — the point the dense encoding could not
    # reach — passes the default 16 GiB pre-flight with room to spare
    est = simx_sweep.check_probe_memory("sparrow", 2000, 50_000, 1, 16 * 2**30)
    assert est < 2**27


def test_sparrow_queue_pick_via_pallas_kernel_matches_ref(small):
    """The head-of-queue pick routed through the Pallas rank-and-select
    kernel (interpreted on the CPU, block_rows=1 for the narrow [W, R] rows)
    reproduces the jnp reference path bitwise."""
    from repro.simx.megha import default_match_fn

    tasks = small
    cfg = SimxConfig(num_workers=48, dt=0.02)
    rounds = min(engine.estimate_rounds(cfg, tasks), 150)
    ref_run = simx_sparrow.simulate_fixed(cfg, tasks, 1, rounds)
    pal_run = simx_sparrow.simulate_fixed(
        cfg, tasks, 1, rounds,
        match_fn=default_match_fn(use_pallas=True, block_rows=1),
    )
    assert jnp.array_equal(ref_run.task_finish, pal_run.task_finish)
    assert jnp.array_equal(ref_run.worker_finish, pal_run.worker_finish)


# ---------------------------------------------------------------------------
# the queue passes at the queues' live width
# ---------------------------------------------------------------------------


def packed_queues(fill, num_jobs, R, seed=0):
    """int32[W, R] queues packed to the left: row w holds ``fill[w]``
    random job ids, then the empty sentinel ``num_jobs``."""
    rng = np.random.default_rng(seed)
    fill = np.asarray(fill)
    q = rng.integers(0, num_jobs, (fill.size, R))
    return jnp.asarray(
        np.where(np.arange(R)[None, :] < fill[:, None], q, num_jobs), jnp.int32
    )


def test_queue_widths_end_at_the_cap(monkeypatch):
    assert simx_sparrow.queue_widths(64) == (16, 64)
    assert simx_sparrow.queue_widths(16) == (16,)
    assert simx_sparrow.queue_widths(3) == (3,)
    monkeypatch.setattr(simx_sparrow, "QUEUE_WIDTHS", (8, 16, 32))
    assert simx_sparrow.queue_widths(64) == (8, 16, 32, 64)
    assert simx_sparrow.queue_widths(20) == (8, 16, 20)


@pytest.mark.parametrize("narrow", [None, (8, 16, 32)], ids=["default", "three"])
@pytest.mark.parametrize(
    "longest", [0, 1, 8, 9, 16, 17, 32, 33, 63, 64, "hole"]
)
def test_width_helper_covers_the_widest_queue(longest, narrow, monkeypatch):
    """On packed queues the live width is the longest queue, and
    ``at_width`` runs its pass at the smallest static width covering it:
    all-empty queues take the smallest width, one full row takes R.  A
    lone entry past a gap (a hole) still counts to its own column.  The
    helper holds for any set of narrow widths."""
    if narrow is not None:
        monkeypatch.setattr(simx_sparrow, "QUEUE_WIDTHS", narrow)
    J, W, R = 50, 40, 64
    rng = np.random.default_rng(7)
    if longest == "hole":
        resq = jnp.full((W, R), J, jnp.int32).at[3, 40].set(5)
        want_width = 41
    else:
        fill = rng.integers(0, min(longest, 8) + 1, W)
        fill[rng.integers(0, W)] = longest
        resq = packed_queues(fill, J, R, seed=longest)
        want_width = longest
    width = simx_sparrow.live_width(resq, J)
    assert int(width) == want_width
    ran_at, k = simx_sparrow.at_width(resq, width, lambda q: jnp.int32(q.shape[1]))
    want_k = min(w for w in simx_sparrow.queue_widths(R) if w >= want_width)
    assert int(ran_at) == int(k) == want_k
    if longest == 0:
        assert want_k == simx_sparrow.queue_widths(R)[0]
    if longest == R:
        assert want_k == R
    # the pass sees every entry it would see at full width
    counts, _ = simx_sparrow.at_width(
        resq, width, lambda q: jnp.sum(q < J, axis=1, dtype=jnp.int32)
    )
    np.testing.assert_array_equal(
        np.asarray(counts), np.asarray(jnp.sum(resq < J, axis=1))
    )


def test_at_width_takes_one_width_a_batch():
    """Under ``vmap`` — nested too, as the sweep grids nest it — every
    point's pass runs at one width, the smallest covering the widest queue
    of the whole batch (``batch_max``), so the switch index stays
    unbatched and the program keeps a conditional."""
    J, W, R = 30, 12, 64
    fills = [[3, 0], [20, 5], [1, 9]]
    stack = jnp.stack([
        jnp.stack([packed_queues(np.full(W, f), J, R, seed=f) for f in row])
        for row in fills
    ])                                                  # int32[3, 2, W, R]

    def k_of(q):
        return simx_sparrow.at_width(
            q, simx_sparrow.live_width(q, J), lambda p: jnp.int32(p.shape[1])
        )[1]

    np.testing.assert_array_equal(np.asarray(jax.vmap(k_of)(stack[0])), [16, 16])
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(jax.vmap(k_of))(stack)), np.full((3, 2), R)
    )
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(simx_sparrow.batch_max)(jnp.arange(6))), np.full(6, 5)
    )
    text = jax.jit(jax.vmap(lambda q: simx_sparrow.at_width(
        q, simx_sparrow.live_width(q, J), lambda p: jnp.sum(p < J, axis=1))[0]
    )).lower(stack[1]).compile().as_text()
    assert " conditional(" in text


def retired_compact_queues(resq, task_finish, t, job_start, job_end):
    """The full-width compaction the rules ran before the queue passes
    followed the live width: every pass over all R columns."""
    W, R = resq.shape
    num_jobs = job_start.shape[0]
    unfinished = simx_sparrow.job_counts(task_finish > t, job_start, job_end)[2]
    unfinished = jnp.append(unfinished, 0)
    live = (resq < num_jobs) & (unfinished[jnp.minimum(resq, num_jobs)] > 0)
    pos = jnp.cumsum(live, axis=1) - 1
    w_rows = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[:, None], (W, R))
    out = (
        jnp.full((W, R), num_jobs, jnp.int32)
        .at[w_rows, jnp.where(live, pos, R)]
        .set(resq, mode="drop")
    )
    return out, jnp.sum(live, axis=1, dtype=jnp.int32)


def retired_insert_probes(resq, fill, targets, jobs, ins):
    """Insertion with the held test over all R columns of each target's
    queue, as before the passes followed the live width."""
    W, R = resq.shape
    C = targets.shape[0]
    c_row = jnp.arange(C, dtype=jnp.int32)
    tw0 = jnp.where(ins, targets, W)
    o0 = jnp.argsort(tw0, stable=True)
    st0, sj0 = tw0[o0], jobs[o0]
    dup_s = (st0 == jnp.roll(st0, 1)) & (sj0 == jnp.roll(sj0, 1))
    dup_s = dup_s.at[0].set(False)
    dup = jnp.zeros(C, jnp.bool_).at[o0].set(dup_s)
    held = jnp.any(resq[jnp.clip(tw0, 0, W - 1)] == jobs[:, None], axis=1)
    keep = ins & ~dup & ~held
    tw = jnp.where(keep, targets, W)
    order = jnp.argsort(tw, stable=True)
    stw = tw[order]
    first = jnp.searchsorted(stw, stw, side="left").astype(jnp.int32)
    rank = jnp.zeros(C, jnp.int32).at[order].set(c_row - first)
    slot = fill[jnp.clip(tw, 0, W - 1)] + rank
    resq = resq.at[tw, slot].set(jobs, mode="drop")
    return resq, jnp.sum(keep & (slot >= R), dtype=jnp.int32)


def retired_queue_heads(resq, pending, match_fn, *, idle=None, dead=None):
    """Late binding's queue passes as the rules ran them inline at full
    width: the ``[W, R]`` pending gather, the head pick's rank-and-select
    and the orphan test's ``pred`` scatter-max."""
    W, _ = resq.shape
    J = pending.shape[0]
    pend_q = jnp.append(pending, 0)[jnp.minimum(resq, J)]
    active = (resq < J) & (pend_q > 0)
    if idle is not None:
        active = active & idle[:, None]
    picked = match_fn(active, jnp.ones(W, jnp.int32)) == 0
    head = jnp.take_along_axis(resq, jnp.argmax(picked, axis=1)[:, None], axis=1)
    job_pick = jnp.where(jnp.any(picked, axis=1), head[:, 0], J)
    exists = resq < J
    if dead is not None:
        exists = exists & ~dead[:, None]
    reserved = (
        jnp.zeros(J, jnp.bool_).at[resq.ravel()].max(exists.ravel(), mode="drop")
    )
    return job_pick, reserved, jnp.int32(1)


@pytest.mark.parametrize("longest", [5, 16, 17, 40, 64])
def test_insert_probes_matches_full_width_held_test(longest):
    """Insertion equals the retired full-width held test when edges repeat
    a job already queued deep in the target's queue (eagle's SSS re-routes
    make such repeats): merged, not inserted, at every width."""
    rng = np.random.default_rng(longest)
    J, W, R, C = 120, 24, 64, 80
    fill = rng.integers(0, min(longest, 12) + 1, W)
    fill[:3] = longest
    q = np.full((W, R), J, np.int64)
    for w in range(W):
        q[w, : fill[w]] = np.sort(rng.choice(J, fill[w], replace=False))
    jobs = np.sort(rng.integers(0, J, C))
    targets = rng.integers(0, W, C)
    deep = rng.choice(C, C // 4, replace=False)         # repeats of queued jobs
    for i, e in enumerate(deep):                       # the last entry too
        w = i % 3
        col = longest - 1 if i < 3 else rng.integers(0, longest)
        targets[e], jobs[e] = w, q[w, col]
    order = np.argsort(jobs, kind="stable")
    args = (jnp.asarray(q, jnp.int32), jnp.asarray(fill, jnp.int32),
            jnp.asarray(targets[order], jnp.int32),
            jnp.asarray(jobs[order], jnp.int32), jnp.arange(C) < C - 7)
    new_q, new_over = simx_sparrow.insert_probes(*args)
    old_q, old_over = retired_insert_probes(*args)
    np.testing.assert_array_equal(np.asarray(new_q), np.asarray(old_q))
    assert int(new_over) == int(old_over)


@pytest.fixture
def retired_queue_passes(monkeypatch):
    """``install()`` rebuilds sparrow and eagle on the retired full-width
    queue passes: compaction, insertion and late binding's head pick and
    orphan test.  Also returns the list of patched calls made since."""
    calls = []

    def compact(*a):
        calls.append("compact")
        return retired_compact_queues(*a)

    def insert(*a):
        calls.append("insert")
        return retired_insert_probes(*a)

    def heads(*a, **k):
        calls.append("heads")
        return retired_queue_heads(*a, **k)

    def install():
        for mod in (simx_sparrow, simx_eagle):
            monkeypatch.setattr(mod, "compact_queues", compact)
            monkeypatch.setattr(mod, "insert_probes", insert)
            monkeypatch.setattr(mod, "queue_heads", heads)

    return install, calls


def burst_tasks(num_jobs, num_workers, seed, squeeze=0.05):
    """A trace whose arrivals are squeezed into a burst, so that every
    worker's queue grows long; a quarter of the jobs are eagle's long
    jobs."""
    wl = synthetic_trace(num_jobs=num_jobs, tasks_per_job=8, load=1.0,
                         num_workers=num_workers, seed=seed)
    tasks = export_workload(wl)
    est = np.asarray(tasks.job_est).copy()
    est[::4] = 99.0
    return dataclasses.replace(
        tasks, submit=tasks.submit * squeeze,
        job_submit=tasks.job_submit * squeeze, job_est=jnp.asarray(est),
    )


def assert_states_equal(new, old):
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(old)
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )


@pytest.mark.parametrize("case", ["clean", "crashes", "undersized", "crossing"])
@pytest.mark.parametrize("mod", [simx_sparrow, simx_eagle])
def test_live_width_matches_full_width_bitwise(mod, case, retired_queue_passes):
    """Sparrow and eagle reach the retired full-width queue passes' final
    state, every field and counter, bit for bit: on a plain trace, under a
    wave of worker crashes, with a cap so small that queues fill to R and
    overflow, and on a burst whose queues cross from the narrow width to
    the cap R = 64 and back."""
    if case == "crossing":
        tasks = burst_tasks(80, 16, seed=4)
        cfg = SimxConfig(num_workers=16, dt=0.02, reserve_cap=64,
                         long_threshold=10.0)
    else:
        wl = synthetic_trace(num_jobs=24, tasks_per_job=16, load=0.9,
                             num_workers=32, seed=6)
        tasks = export_workload(wl)
        est = np.asarray(tasks.job_est).copy()
        est[::4] = 99.0
        tasks = dataclasses.replace(tasks, job_est=jnp.asarray(est))
        cap = 3 if case == "undersized" else 40
        cfg = SimxConfig(num_workers=32, dt=0.02, reserve_cap=cap,
                         long_threshold=10.0)
    W = cfg.num_workers
    faults = None
    if case == "crashes":
        rng = np.random.default_rng(5)
        down = np.full(W, np.inf, np.float32)
        hit = rng.permutation(W)[: W // 2]
        down[hit] = np.linspace(0.2, 3.0, hit.size).astype(np.float32)
        faults = empty_schedule(W, cfg.num_gms).replace(
            worker_down=jnp.asarray(down), worker_up=jnp.asarray(down + 0.1)
        )
    rounds = engine.estimate_rounds(cfg, tasks, slack=4.0)
    new, tl = simx_runtime.simulate_fixed(
        mod.RULE.name, cfg, tasks, 3, rounds, faults=faults,
        telemetry=TelemetryConfig(stride=1),
    )
    at_cap = int(np.sum(tl.series["full_width_rounds"]))
    if case == "crossing":
        assert 0 < at_cap < rounds // 2, at_cap
    if case == "undersized":
        assert at_cap == rounds                     # R = 3: no narrow width
        assert int(new.res_overflow) > 0
    if case in ("clean", "crashes"):
        assert at_cap == 0
    if case == "crashes":
        assert int(new.lost) > 0
    install, calls = retired_queue_passes
    install()
    old = mod.simulate_fixed(cfg, tasks, 3, rounds, faults=faults)
    assert {"compact", "insert", "heads"} <= set(calls)
    assert_states_equal(new, old)


@pytest.mark.parametrize("rule", ["sparrow", "eagle"])
def test_live_width_matches_full_width_streamed(rule, retired_queue_passes):
    """The streaming window (``make_<rule>_step(layout=...)``, the queues
    remapped on the host between refills) replays the retired full-width
    queue passes bit for bit: delays, counters and every refill's
    ledger."""
    wl = synthetic_trace(num_jobs=40, tasks_per_job=8, load=1.0,
                         num_workers=16, seed=3)
    wl = Workload(name="burst", jobs=[                 # arrivals in a burst
        Job(job_id=j.job_id, submit_time=j.submit_time * 0.05,
            durations=list(j.durations))
        for j in wl.jobs
    ])
    # pick_fn given: a fresh segment, not the memoized default one
    kw = dict(window_jobs=32, window_tasks=256, rounds_per_refill=16,
              reserve_cap=32, dt=0.02, seed=0,
              pick_fn=simx_runtime.default_match_fn(block_rows=1),
              telemetry=TelemetryConfig(stride=1))
    new = run_steady_state(rule, ReplayArrivals(wl), 16, **kw)
    at_cap = np.asarray(new.timeline.series["full_width_rounds"])
    assert 0 < at_cap.sum() < at_cap.size          # both widths ran
    install, calls = retired_queue_passes
    install()
    old = run_steady_state(rule, ReplayArrivals(wl), 16, **kw)
    assert {"compact", "insert", "heads"} <= set(calls)
    assert new.tasks_completed == wl.num_tasks
    assert np.array_equal(new.delays, old.delays)
    assert (new.tasks_completed, new.probes, new.messages, new.rounds) == (
        old.tasks_completed, old.probes, old.messages, old.rounds)
    for k in new.series:
        assert np.array_equal(new.series[k], old.series[k], equal_nan=True), k
    assert new.refills == old.refills


@pytest.mark.parametrize("rule", ["sparrow", "eagle"])
def test_live_width_matches_full_width_vmapped(rule, retired_queue_passes):
    """The sweep grid (``sweep_grid``: a vmap over loads of a vmap over
    seeds) gives the retired full-width passes' summaries bit for bit, and
    its optimized program keeps the width conditionals of compaction,
    insertion's held test and late binding: one width a batch, so no
    select runs every branch."""
    kw = dict(loads=(0.5, 0.95), num_seeds=2, num_workers=64, num_jobs=16,
              tasks_per_job=24, dt=0.02, trace_seed=3, reserve_cap=64,
              long_threshold=10.0)
    plan = simx_sweep.fig2_plan(rule, **kw)
    args = (plan.name, plan.cfg, plan.tasks, plan.submit_grid,
            plan.job_submit_grid, plan.seeds, plan.num_rounds)
    new = simx_sweep.sweep_grid(*args, pick_fn=plan.pick_fn)
    text = jax.jit(jax.vmap(jax.vmap(
        lambda sub, seed: simx_runtime.simulate_fixed(
            plan.name, plan.cfg,
            dataclasses.replace(plan.tasks, submit=sub), seed, 4,
            pick_fn=plan.pick_fn,
        ),
        in_axes=(None, 0)), in_axes=(0, None),
    )).lower(plan.submit_grid, plan.seeds).compile().as_text()
    assert text.count(" conditional(") == 3
    install, calls = retired_queue_passes
    install()
    old = simx_sweep.sweep_grid(*args, pick_fn=plan.pick_fn)
    assert {"compact", "insert", "heads"} <= set(calls)
    assert int(np.min(new["tasks_done"])) == plan.tasks.num_tasks
    for k in new:
        np.testing.assert_array_equal(np.asarray(new[k]), np.asarray(old[k]), err_msg=k)
