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
* carried state is independent of the trace length.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.simx import SimxConfig, empty_schedule, engine, export_workload
from repro.simx import eagle as simx_eagle
from repro.simx import sparrow as simx_sparrow
from repro.simx import sweep as simx_sweep
from repro.simx.state import (
    init_eagle_state,
    init_sparrow_state,
    probe_edge_layout,
)
from repro.workload.synth import synthetic_trace


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
