"""Sparrow transition rule for the simx round-stepped backend.

Vectorized batch sampling + late binding (§2.2.2).  When a job of n tasks
arrives it probes ``min(d * n, W)`` DISTINCT random workers (the event
backend's ``rng.sample`` semantics), leaving a *reservation* at each.
Tasks are NOT bound to workers: each round, every idle worker serves the
earliest-submitted job holding a reservation on it that still has pending
tasks, and late binding hands it that job's next pending task.
Reservations of fully launched jobs act cancelled — the ``pending > 0``
test skips them, like the event backend's cancel RPC.

**Reservation encoding** — capped per-worker queues, not a dense mask:
``resq int32[W, R]`` holds each worker's reservations as job ids (J =
empty), with ``R = cfg.queue_cap(...)`` a small static cap.  Probes live
in a static *edge list* sorted by job id (== submit order) and are
inserted through a ``cfg.insert_window(...)``-wide head window each round
(the megha FIFO-window trick), entries are recycled when their job
completes, and the queues are re-compacted every round so they stay
ascending in job id — which makes the head-of-queue pick (earliest live
reservation) exactly a rank-and-select with ``n = 1`` per worker row,
routed through the same (Pallas-capable) ``match_fn`` primitive as
megha's GM match.  Carried probe state is O(W * R) — independent of the
trace length — plus O(d * T) static edge constants (the same order as the
task arrays themselves); nothing is ever materialized at [J, W].

**Per-job counts** — tasks are laid out contiguously per job, so a job's
pending or unfinished count is one prefix sum over the [T] task mask read
at the job's two boundaries (``job_counts``), and late binding finds a
job's r-th pending task by a W-wide binary search of that prefix sum.  A
round runs two [T] prefix sums (unfinished, pending) and no [T]-wide
scatter.

**Queue width** — the queues are packed to the left (compaction slides
live entries to the front, insertion appends at ``fill``), so every
column past the longest queue is empty.  Each per-worker queue pass
(compaction, insertion's held test, the head pick and the orphan test)
runs on the queues' leading ``k`` columns only, ``k`` the smallest of a
few static widths (``QUEUE_WIDTHS``, then R) that covers the widest
queue this round (``at_width``): the same results, at a fraction of the
slots while the queues run short of the cap.  Under
``vmap`` (the sweep grids) one width covers the whole batch, so the
width's ``lax.switch`` stays a conditional (``batch_max``).

Approximations vs. the event backend (beyond round quantization, see
``engine``): a worker whose chosen job runs out of pending tasks this
round (more claimants than tasks) retries next round instead of popping
the next reservation within the same 0.5 ms RPC; probe insertion is
windowed, so an arrival burst wider than the window lands over the
following rounds (the auto window drains a whole-trace burst in ~32
rounds; saturated rounds are counted in ``probe_lag``); and a probe
aimed at a worker whose queue is full is dropped (counted in
``res_overflow``) — the
orphan-rescue path keeps a job schedulable even if every one of its
probes was dropped, so an undersized R degrades placement quality, never
liveness.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx import runtime as rt, spans
from repro.simx.faults import (
    FaultSchedule,
    jobs_with_reservation,
    worker_dead,
)
from repro.simx.runtime import MatchFn, default_match_fn
from repro.simx.state import (
    SimxConfig,
    SparrowState,
    TaskArrays,
    init_sparrow_state,
    probe_edge_layout,
    spec,
)


def job_bounds(tasks: TaskArrays) -> tuple[jax.Array, jax.Array]:
    """``(start, end) int32[J]``: job j owns task slots ``[start[j],
    end[j])``.  Tasks are exported contiguously per job, in job order
    (``export_workload``; the streaming window keeps the layout with a pad
    job that owns the trailing slots), so the boundaries are the running
    task counts."""
    end = jnp.cumsum(tasks.job_ntasks, dtype=jnp.int32)
    return end - tasks.job_ntasks, end


def check_contiguous(tasks: TaskArrays) -> None:
    """Host-side guard on a concrete trace: raise unless every job's tasks
    form one slice, in job order — the layout ``job_counts`` reads."""
    job = np.asarray(tasks.job)
    ntasks = np.asarray(tasks.job_ntasks)
    if not np.array_equal(job, np.repeat(np.arange(ntasks.size, dtype=job.dtype), ntasks)):
        raise ValueError("tasks must be laid out contiguously per job, in job order")


def job_counts(
    mask: jax.Array, job_start: jax.Array, job_end: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-job counts of a per-task mask, without a [T]-wide scatter.

    Requires the contiguous per-job layout (``job_bounds``): one prefix
    sum ``c`` over the mask, read at each job's two boundaries.  Returns
    ``(c int32[T], base int32[J], count int32[J])`` where ``base[j]`` is
    the number of set entries before job j's slice and ``count[j]`` the
    number inside it."""
    c = jnp.cumsum(mask, dtype=jnp.int32)

    def before(i):
        return jnp.where(i > 0, c[jnp.maximum(i - 1, 0)], 0)

    base = before(job_start)
    return c, base, before(job_end) - base


def late_bind(
    job_pick: jax.Array, c: jax.Array, base: jax.Array, pending: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Late-binding core shared by the sparrow and eagle rules: worker ``w``
    serves job ``job_pick[w]`` (``J`` = no claim); the k-th serving worker of
    job j (worker-index order, capped at j's pending count) gets j's k-th
    pending task.  ``(c, base, pending)`` is ``job_counts`` of the pending
    mask, so tasks must be laid out contiguously per job.  Returns
    ``(launch bool[W], task int32[W])`` with ``T`` meaning none.

    Serve ranks come from one stable sort of ``job_pick`` and a running
    max of the sorted runs' first positions.  Pending tasks keep their index
    order, so j's r-th pending task is where ``c`` first exceeds
    ``base[j] + r``: a W-wide binary search of ``c`` (log T steps),
    which lands inside j's slice because ``r < pending[j]``.  No [T]-wide
    array is built.  Bitwise-equal to the retired dense [J, W]
    formulation — ``tests/test_simx_queues.py`` pins this against an
    in-test dense reference, holes in the pending mask included.
    """
    T = c.shape[0]
    W = job_pick.shape[0]
    J = base.shape[0]
    w_row = jnp.arange(W, dtype=jnp.int32)
    order = jnp.argsort(job_pick, stable=True)
    sj = job_pick[order]
    run_head = jnp.concatenate([jnp.ones(1, jnp.bool_), sj[1:] != sj[:-1]])
    first = jax.lax.cummax(jnp.where(run_head, w_row, 0))
    rank = jnp.zeros(W, jnp.int32).at[order].set(w_row - first)
    jp = jnp.clip(job_pick, 0, J - 1)
    serve = (job_pick < J) & (rank < pending[jp])
    task = jnp.searchsorted(c, base[jp] + rank, side="right").astype(jnp.int32)
    return serve, jnp.where(serve, task, T)


def probe_targets(
    key: jax.Array, cfg: SimxConfig, tasks: TaskArrays, kmax: int
) -> jax.Array:
    """int32[J, kmax] — per-job probe targets; row j's first k_j entries are
    a uniform ordered sample of k_j DISTINCT workers (``rng.sample``
    semantics: the kmax largest of W iid uniform scores, whose descending
    order is a uniform k-permutation).  Exactly kmax indices per row by
    construction — duplicate scores cannot widen the selection the way the
    old ``scores <= kth`` threshold mask could.

    Rows are generated in chunks through ``lax.map`` so the transient
    [chunk, W] score buffer stays a few MB no matter how long the trace is
    (the retired dense path materialized [J, W] here).
    """
    J, W = tasks.num_jobs, cfg.num_workers
    if kmax <= 0 or J == 0:
        return jnp.zeros((J, max(kmax, 0)), jnp.int32)
    chunk = int(max(1, min(J, (1 << 21) // max(W, 1))))
    n_chunks = -(-J // chunk)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_chunks))

    def sample(k):
        scores = jax.random.uniform(k, (chunk, W))
        return jax.lax.top_k(scores, kmax)[1].astype(jnp.int32)

    rows = jax.lax.map(sample, keys)                    # [n_chunks, chunk, kmax]
    return rows.reshape(n_chunks * chunk, kmax)[:J]


def probe_mask(key: jax.Array, cfg: SimxConfig, tasks: TaskArrays) -> jax.Array:
    """bool[J, W] — the min(d * n_tasks, W) DISTINCT workers each job probes.

    Dense *reference* view of ``probe_targets`` (one scatter of the target
    table), kept for tests and offline analysis — the transition rules
    never materialize it.  Rank-based by construction: each row holds
    exactly min(d * n_tasks, W) probes even on duplicate uniform scores,
    where the old ``scores <= kth`` threshold could select more on ties.
    """
    J, W = tasks.num_jobs, cfg.num_workers
    kvec = jnp.minimum(cfg.probe_ratio * tasks.job_ntasks, W)       # int32[J]
    kmax = int(min(cfg.probe_ratio * int(np.max(np.asarray(tasks.job_ntasks), initial=0)), W))
    targets = probe_targets(key, cfg, tasks, kmax)
    take = jnp.arange(kmax, dtype=jnp.int32)[None, :] < kvec[:, None]
    return (
        jnp.zeros((J, W), jnp.bool_)
        .at[jnp.arange(J, dtype=jnp.int32)[:, None], jnp.where(take, targets, W)]
        .set(True, mode="drop")
    )


def build_probe_edges(
    key: jax.Array, cfg: SimxConfig, tasks: TaskArrays, short_only: bool = False
) -> tuple[jax.Array, jax.Array, jax.Array, int, int]:
    """Materialize the flat probe edge list the windowed insertion walks.

    Samples the per-job target table (``probe_targets``) and gathers it
    through the concrete ``probe_edge_layout``; both the job and worker
    arrays are padded by the window width C so the head window's
    ``dynamic_slice`` stays in bounds at head == P (pad jobs never
    "arrive").  Returns ``(edge_job[P+C], edge_worker[P+C],
    edge_end[J], P, C)``.
    """
    J = tasks.num_jobs
    edge_job_np, edge_rank_np, edge_end_np, kmax = probe_edge_layout(
        cfg, tasks, short_only=short_only
    )
    P = int(edge_job_np.size)
    C = cfg.insert_window(P, kmax)
    if P:
        targets = probe_targets(key, cfg, tasks, kmax)
        workers = targets[jnp.asarray(edge_job_np), jnp.asarray(edge_rank_np)]
    else:
        workers = jnp.zeros(0, jnp.int32)
    edge_worker = jnp.concatenate([workers, jnp.zeros(C, jnp.int32)])
    edge_job = jnp.concatenate(
        [jnp.asarray(edge_job_np), jnp.full(C, J, jnp.int32)]
    )
    return edge_job, edge_worker, jnp.asarray(edge_end_np), P, C


def probe_window_slice(
    edge_job: jax.Array,
    edge_worker: jax.Array,
    head: jax.Array,
    window: int,
    job_submit_pad: jax.Array,
    t: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One round's view of the edge list: the ``window`` edges at ``head``
    and their ready prefix.  Submit times are sorted by job id, so
    readiness is a prefix — ``lead`` edges insert this round and the head
    advances by it.  Returns ``(win_job, win_worker, lead, ins mask,
    lagged bool[])`` where ``lagged`` means a ready edge was left beyond
    the full window, i.e. this round's insertion actually delayed a probe
    (an exact-fit window is not lag)."""
    J = job_submit_pad.shape[0] - 1
    win_j = jax.lax.dynamic_slice(edge_job, (head,), (window,))
    win_w = jax.lax.dynamic_slice(edge_worker, (head,), (window,))
    ready = job_submit_pad[jnp.minimum(win_j, J)] <= t
    lead = jnp.sum(jnp.cumprod(ready.astype(jnp.int32)), dtype=jnp.int32)
    ins = jnp.arange(window, dtype=jnp.int32) < lead
    # the first edge past the window: pad edges read as never-ready, so a
    # clipped gather is safe at the tail of the list
    nxt = edge_job[jnp.minimum(head + window, edge_job.shape[0] - 1)]
    lagged = (lead == window) & (job_submit_pad[jnp.minimum(nxt, J)] <= t)
    return win_j, win_w, lead, ins, lagged


def insert_probes(
    resq: jax.Array,
    fill: jax.Array,
    targets: jax.Array,
    jobs: jax.Array,
    ins: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Scatter this round's probe edges into the per-worker queues.

    ``targets``/``jobs`` are the window's edge targets and job ids,
    ``ins`` masks the ready prefix.  A probe landing where the same job
    already holds (or this round gains) a reservation *merges* — one
    queue entry, like the dense bool-mask encoding it replaced; eagle's
    SSS re-routes are the only producer of such collisions (sparrow
    targets are distinct per job).  Kept edges are appended after the
    ``fill`` existing entries of each queue; same-round edges aimed at
    one worker get consecutive slots via a stable sort by target (which
    also preserves the window's ascending-job order, keeping every queue
    sorted by job id).  Edges whose slot lands past R are dropped —
    returns ``(resq, n_overflow)``; merged duplicates are neither
    inserted nor counted as overflow.  The held test reads the queues at
    the width of their longest ``fill`` (``at_width``).
    """
    W, R = resq.shape
    C = targets.shape[0]
    c_row = jnp.arange(C, dtype=jnp.int32)
    tw0 = jnp.where(ins, targets, W)
    # same-round duplicates: the stable target sort keeps ascending job
    # order within each target group, so (job, target) repeats are adjacent
    o0 = jnp.argsort(tw0, stable=True)
    st0, sj0 = tw0[o0], jobs[o0]
    dup_s = (st0 == jnp.roll(st0, 1)) & (sj0 == jnp.roll(sj0, 1))
    dup_s = dup_s.at[0].set(False)
    dup = jnp.zeros(C, jnp.bool_).at[o0].set(dup_s)
    # earlier-round duplicates: the job already queued on this worker
    # (entries lie in the first ``fill`` columns: run at their width)
    tw0_row = jnp.clip(tw0, 0, W - 1)
    held, _ = at_width(
        resq, jnp.max(fill),
        lambda q: jnp.any(q[tw0_row] == jobs[:, None], axis=1),
    )
    keep = ins & ~dup & ~held
    tw = jnp.where(keep, targets, W)
    order = jnp.argsort(tw, stable=True)
    stw = tw[order]
    first = jnp.searchsorted(stw, stw, side="left").astype(jnp.int32)
    rank = jnp.zeros(C, jnp.int32).at[order].set(c_row - first)
    slot = fill[jnp.clip(tw, 0, W - 1)] + rank
    resq = resq.at[tw, slot].set(jobs, mode="drop")     # tw==W / slot>=R drop
    return resq, jnp.sum(keep & (slot >= R), dtype=jnp.int32)


#: The static widths a per-worker queue pass may run at below the cap R,
#: which always comes last (``queue_widths``).  Each one compiles the
#: narrow passes once more, 1 to 3 s more for the TPU at 50,000 workers,
#: so there is one: on the paper's 50,000-worker trace at load 0.8 the
#: longest queue holds 8 to 14 entries.
QUEUE_WIDTHS = (16,)


def queue_widths(R: int) -> tuple[int, ...]:
    """The static widths ``at_width`` chooses from for a cap of R slots."""
    return tuple(k for k in QUEUE_WIDTHS if k < R) + (R,)


@jax.custom_batching.custom_vmap
def batch_max(x: jax.Array) -> jax.Array:
    """The largest element of ``x``; under ``vmap``, of the whole batch's
    ``x``, returned unbatched."""
    return jnp.max(x)


@batch_max.def_vmap
def _batch_max_vmap(axis_size, in_batched, x):
    return batch_max(x), False


def live_width(resq: jax.Array, num_jobs: int) -> jax.Array:
    """int32[] — one past the last queue column holding any entry
    (``num_jobs`` = empty): every column from here on is empty in every
    queue.  An elementwise reduction over [W, R]: no gather, no scatter."""
    R = resq.shape[1]
    held = jnp.any(resq < num_jobs, axis=0)                     # bool[R]
    return jnp.max(jnp.where(held, jnp.arange(1, R + 1, dtype=jnp.int32), 0))


def at_width(
    resq: jax.Array, width: jax.Array, fn: Callable[[jax.Array], object]
) -> tuple[object, jax.Array]:
    """Run a per-worker queue pass on the queues' leading columns only.

    ``fn(resq[:, :k])`` runs for the smallest static ``k`` of
    ``queue_widths(R)`` with ``k >= width``, one ``lax.switch`` branch a
    width; ``width`` must cover every entry, so the pass sees the same
    entries it would see at full width.  Its results must not depend on
    ``k``'s shape (a pass that rewrites the queues returns them at
    ``[W, R]``).  Under ``vmap`` ``k`` covers the whole batch's widths
    (``batch_max``): a batched index would turn the switch into a select
    that runs every branch.  Returns ``(fn's result, k int32[])``."""
    widths = queue_widths(resq.shape[1])
    if len(widths) == 1:
        return fn(resq), jnp.int32(widths[0])
    i = jnp.sum(jnp.asarray(widths[:-1]) < batch_max(width), dtype=jnp.int32)
    out = jax.lax.switch(i, [lambda k=k: fn(resq[:, :k]) for k in widths])
    return out, jnp.asarray(widths, jnp.int32)[i]


def compact_queues(
    resq: jax.Array,
    task_finish: jax.Array,
    t: jax.Array,
    job_start: jax.Array,
    job_end: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Recycle queue slots of completed jobs and re-compact each queue.

    An entry lives while its job still has an unfinished task (launched-
    but-running included, so a crash re-pending a task finds the job's
    reservations intact); live entries slide to the front preserving
    order.  Tasks must be laid out contiguously per job: ``job_start`` /
    ``job_end`` (``job_bounds``) turn one prefix sum into the per-job
    unfinished counts.  The pass runs at the queues' live width
    (``at_width``).  Returns ``(resq, fill int32[W])``.
    """
    W, R = resq.shape
    num_jobs = job_start.shape[0]
    unfinished = job_counts(task_finish > t, job_start, job_end)[2]
    unfinished = jnp.append(unfinished, 0)                      # J = empty

    def compact(q):
        k = q.shape[1]
        live = (q < num_jobs) & (unfinished[jnp.minimum(q, num_jobs)] > 0)
        pos = jnp.cumsum(live, axis=1) - 1
        w_rows = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[:, None], (W, k))
        out = (
            jnp.full((W, R), num_jobs, jnp.int32)   # columns k: hold no entry
            .at[w_rows, jnp.where(live, pos, R)]
            .set(q, mode="drop")
        )
        return out, jnp.sum(live, axis=1, dtype=jnp.int32)

    return at_width(resq, live_width(resq, num_jobs), compact)[0]


def queue_head_pick(
    resq: jax.Array, active: jax.Array, match_fn: MatchFn, num_jobs: int
) -> jax.Array:
    """int32[W] — each worker's head-of-queue job (J = none): the first
    active entry of its compacted, job-id-ordered queue, i.e. the
    earliest-submitted job with pending work holding a reservation here.

    Expressed as rank-and-select with ``n = 1`` per worker row so the
    pick runs through the same primitive as megha's GM match — the jnp
    cumsum reference on CPU, the batched Pallas kernel on TPU (pass a
    ``match_fn`` built with ``block_rows=1``: queue rows are R ≲ 64 wide,
    and the kernel pads rows to ``block_rows * 128`` lanes).
    """
    W = resq.shape[0]
    ranks = match_fn(active, jnp.ones(W, jnp.int32))    # int32[W, R]
    picked = ranks == 0
    slot = jnp.argmax(picked, axis=1)
    head = jnp.take_along_axis(resq, slot[:, None], axis=1)[:, 0]
    return jnp.where(jnp.any(picked, axis=1), head, num_jobs)


def queue_heads(
    resq: jax.Array,
    pending: jax.Array,
    match_fn: MatchFn,
    *,
    idle: jax.Array | None = None,
    dead: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Late binding's queue passes, shared by the sparrow and eagle rules.

    An entry is active while its job has pending tasks (``pending
    int32[J]``; with ``idle``, only on idle workers).  Returns
    ``(job_pick int32[W], reserved bool[J], at_cap int32[])``: each
    worker's head-of-queue job (``queue_head_pick``), the jobs holding an
    entry on a live worker (``jobs_with_reservation``), and 1 where the
    passes ran at the cap R, else 0 (the ``full_width_rounds`` counter;
    under ``vmap``, for the batch's widest queue).  The width is read
    here, after this round's insertion has appended to the queues
    (``at_width``)."""
    J = pending.shape[0]
    pend_j = jnp.append(pending, 0)                             # J = empty

    def heads(q):
        active = (q < J) & (pend_j[jnp.minimum(q, J)] > 0)
        if idle is not None:
            active = active & idle[:, None]
        return (
            queue_head_pick(q, active, match_fn, J),
            jobs_with_reservation(q, J, dead=dead),
        )

    (job_pick, reserved), k = at_width(resq, live_width(resq, J), heads)
    return job_pick, reserved, (k == resq.shape[1]).astype(jnp.int32)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ProbeLayout:
    """Traced per-window probe edge list for the streaming engine.

    The fixed path samples the probe targets once and bakes the edge list
    into the step as closure constants; the streaming engine passes them
    as *traced* arrays so one compiled step serves every refilled window.
    Targets are host-sampled per *global* job id at admission, so a job
    carried across refills keeps the same probed workers.  Pad edges past
    the window's real edge count carry ``edge_job == J`` (the pad job
    never "arrives", so the ready prefix — and with it the probe/message
    counters — stays exact); ``edge_end`` of jobs without probes (and of
    the pad job slot) points past every real edge.  ``window`` is the
    static insertion width C the lists were padded for.
    """

    edge_job: jax.Array = spec("int32[?]")     # P_cap + window edges
    edge_worker: jax.Array = spec("int32[?]")  # same length as edge_job
    edge_end: jax.Array = spec("int32[J]")
    window: int = dataclasses.field(metadata=dict(static=True))


@spans.span("simx.build")
def make_sparrow_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    key: jax.Array,
    match_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
    layout: Optional[ProbeLayout] = None,
) -> Callable[[SparrowState], SparrowState]:
    """Build the jittable one-round transition function.

    Round order: fault transitions -> queue recycling/compaction ->
    windowed probe insertion -> late binding (idle workers serve their
    queue heads, orphaned jobs rescued by any idle worker).

    With ``faults``, crashed workers lose their in-flight task (it simply
    re-pends — late binding has no head pointer to roll back) and read
    busy until recovery, so they never serve reservations; a pending job
    whose every queue entry sits on a currently-dead worker is *orphaned*
    and temporarily served by any idle worker (the round-space stand-in
    for re-probing after RPC timeouts — without it a never-recovering
    reservation set would strand the job).  ``faults=None`` builds the
    fault-free program; an empty schedule is bit-identical to it.
    """
    if match_fn is None:
        match_fn = default_match_fn()
    W = cfg.num_workers
    T = tasks.num_tasks
    J = tasks.num_jobs
    if layout is None:
        check_contiguous(tasks)
        edge_job, edge_worker, edge_end, P, C = build_probe_edges(key, cfg, tasks)
    else:
        if faults is not None:
            raise NotImplementedError(
                "streaming layout does not compose with fault schedules"
            )
        edge_job, edge_worker, edge_end = (
            layout.edge_job, layout.edge_worker, layout.edge_end,
        )
        C = layout.window
    job_submit_pad = jnp.concatenate([tasks.job_submit, jnp.float32([jnp.inf])])
    j_idx = jnp.arange(J, dtype=jnp.int32)
    dur_pad = jnp.concatenate([tasks.duration, jnp.float32([0.0])])
    job_start, job_end = job_bounds(tasks)

    def dispatch(s, t, task_finish0, worker_finish0, idle, comp, lost_w):
        # completions are implicit: a worker is idle iff worker_finish <= t
        # (the runtime's completion stage), and task_finish was recorded at
        # launch; a crash-lost task simply re-pends — late binding has no
        # head pointer to roll back, so ``lost_w`` goes unused
        del comp, lost_w

        # -- 0. recycle completed jobs' slots, compact the queues -----------
        with jax.named_scope("simx.sparrow.compact"):
            resq, fill = compact_queues(s.resq, task_finish0, t, job_start, job_end)

        # -- 1. windowed probe insertion (edge list is in arrival order) ----
        with jax.named_scope("simx.sparrow.insert"):
            win_j, win_w, lead, ins, lagged = probe_window_slice(
                edge_job, edge_worker, s.probe_head, C, job_submit_pad, t
            )
            resq, n_over = insert_probes(resq, fill, win_w, win_j, ins)
            head = s.probe_head + lead
            # a ready edge left beyond the window means the burst outran it:
            # count the round so the probe latency is observable (insert_window)
            lag = s.probe_lag + lagged.astype(jnp.int32)
            # every probe RPC counts (and costs a message), kept or dropped
            probes_ctr = s.probes + lead
            messages = s.messages + lead

        # -- 2. late binding: idle workers serve their queue heads ----------
        with jax.named_scope("simx.sparrow.bind"):
            pend_task = jnp.isinf(task_finish0) & (tasks.submit <= t)   # bool[T]
            c, base, pending = job_counts(pend_task, job_start, job_end)
            # orphan rescue: an inserted pending job with no live reservation
            # anywhere (all probes dropped on full queues, or — under faults —
            # every probed worker currently dead) may be served by any idle
            # worker (dead workers never serve: worker_finish holds recovery)
            dead = worker_dead(faults, t) if faults is not None else None
            job_pick, reserved, at_cap = queue_heads(
                resq, pending, match_fn, dead=dead
            )
            orphan = (edge_end <= head) & (pending > 0) & ~reserved
            rescue = jnp.min(jnp.where(orphan, j_idx, J))
            job_pick = jnp.minimum(job_pick, rescue)
            launch, task_pick = late_bind(
                jnp.where(idle, job_pick, J), c, base, pending
            )
            # client->scheduler hop + worker->scheduler get-task RPC round trip
            task_finish, worker_finish, worker_task = rt.apply_launch(
                launch, task_pick, t + 3 * cfg.hop, dur_pad,
                task_finish0, worker_finish0, s.worker_task, T,
            )
            messages = messages + 2 * jnp.sum(launch, dtype=jnp.int32)  # RPC + reply

            upd = dict(
                task_finish=task_finish,
                worker_finish=worker_finish,
                worker_task=worker_task,
                resq=resq,
                probe_head=head,
                res_overflow=s.res_overflow + n_over,
                probe_lag=lag,
                probes=probes_ctr,
                messages=messages,
            )
        if telemetry:
            with jax.named_scope("simx.telemetry"):
                upd["telemetry"] = dict(
                    launches=jnp.sum(launch, dtype=jnp.int32),
                    full_width_rounds=at_cap,
                )
        if provenance:
            with jax.named_scope("simx.provenance"):
                # attempt = a scheduler acted on the job this round: its probes
                # were inserted into reservation queues (``ins`` carries the
                # newly-inserted window prefix) or it was orphan-rescued; the
                # runtime latches the first such round, and or-s in launches.
                # authority = the job's home scheduler (jobs hash round-robin
                # onto the ``num_gms`` stateless Sparrow schedulers).
                att_j = (
                    jnp.zeros(J + 1, jnp.bool_)
                    .at[jnp.where(ins, win_j, J)]
                    .set(True, mode="drop")
                )
                att_j = att_j.at[:-1].max(orphan)
                authority = (
                    tasks.job[jnp.minimum(worker_task, T - 1)] % cfg.num_gms
                ).astype(jnp.int32)
                upd["provenance"] = dict(
                    attempt=att_j[:-1][tasks.job], authority=authority
                )
        return upd

    return rt.compose_step(
        cfg, tasks, dispatch, faults, telemetry=telemetry, provenance=provenance
    )


def simulate_fixed(
    cfg: SimxConfig,
    tasks: TaskArrays,
    seed: jax.Array | int,
    num_rounds: int,
    match_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
) -> SparrowState:
    """Run exactly ``num_rounds`` rounds from an idle DC (vmap-able in
    seed).  ``match_fn`` IS the narrow head-of-queue pick (sparrow has no
    wide match); the registry routes it as ``pick_fn``."""
    return rt.simulate_fixed(
        "sparrow", cfg, tasks, seed, num_rounds, pick_fn=match_fn, faults=faults
    )


def _build_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    key: jax.Array,
    *,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
) -> Callable[[SparrowState], SparrowState]:
    # sparrow's only rank-and-select is the [W, R] head-of-queue pick.
    # When both are supplied (the sweep drivers), pick_fn wins — the wide
    # match_fn's kernel tile would pad every R ≲ 64 queue row to
    # block_rows * 128 lanes.  A bare match_fn (the retired per-module
    # SIMULATE_FIXED signature, where match_fn IS the pick) still routes
    # to the pick rather than being silently dropped.
    return make_sparrow_step(
        cfg, tasks, key, pick_fn if pick_fn is not None else match_fn,
        faults=faults, telemetry=telemetry, provenance=provenance,
    )


RULE = rt.register_rule(
    rt.Rule(
        name="sparrow",
        init=lambda cfg, tasks: init_sparrow_state(cfg, tasks),
        build_step=_build_step,
        has_queues=True,
    )
)
