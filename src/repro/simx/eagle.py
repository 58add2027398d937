"""Eagle transition rule for the simx round-stepped backend.

Hybrid scheduling with Succinct State Sharing (SSS) and sticky batch
probing (paper §2.2.3), reformulated over dense arrays:

  * **Long path** — jobs with ``estimated >= long_threshold`` feed one
    central FIFO over the *long partition* (workers ``[R, W)`` where
    ``R = cfg.short_reserved``).  Each round the central scheduler matches
    its queued window onto free long-partition workers (lowest index first,
    like the event backend's ``min(free)``) with the rank-and-select
    primitive — the same kernel megha's GM match uses, as a 1-row batch.
  * **Short path** — Sparrow-style batch sampling with late binding over
    ALL workers, refined by SSS at probe time: a probe landing on a worker
    currently running a long task is rejected and re-routed once to a
    random worker (standing in for "a node clear in the returned SS
    bit-vector"), and, if rejected again, to the short partition — which
    never runs long tasks, so the second re-route always sticks.
  * **Sticky batch draining** — a worker finishing a task of job ``j``
    immediately pulls ``j``'s next unlaunched task (no new probe, no hop),
    covering both the short sticky-probing rule and the central
    scheduler's same-job preference for long jobs.

**Reservation encoding** — like sparrow, short-job reservations live in
capped per-worker queues ``resq int32[W, R_q]`` fed by a windowed probe
edge list; SSS rejection/re-routing is evaluated *per edge* at insertion
time (one gather + two modular re-targets per probe) instead of over the
dense ``[J, W]`` masks of the retired encoding.  Carried probe state is
O(W * R_q) — independent of the trace length.

Approximations vs. the event backend (beyond round quantization, see
``engine``): probe rejection is evaluated once, at the insertion round
(normally the arrival round; an arrival burst wider than the insertion
window pushes the tail probes — and their SSS test — a few rounds later),
against the ground-truth set of long-running workers at that instant (the
event backend re-sends against a possibly stale SS adopted from the last
rejection); re-routed probes pick targets by a per-job random rotation
rather than a fresh uniform draw; probes aimed at a full queue are
dropped (``res_overflow``; orphan rescue keeps the job schedulable); and
the central scheduler launches only onto workers that are *actually*
free, so a long task waits in the central queue instead of head-of-line
blocking behind a short task already running on its assigned worker.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx import runtime as rt, spans
from repro.simx.faults import FaultSchedule, worker_dead
from repro.simx.runtime import MatchFn, default_match_fn
from repro.simx.sparrow import (
    ProbeLayout,
    build_probe_edges,
    check_contiguous,
    compact_queues,
    insert_probes,
    job_bounds,
    job_counts,
    late_bind,
    probe_mask,
    probe_window_slice,
    queue_heads,
)
from repro.simx.state import (
    EagleState,
    SimxConfig,
    TaskArrays,
    init_eagle_state,
    spec,
)


def eagle_probe_mask(key: jax.Array, cfg: SimxConfig, tasks: TaskArrays) -> jax.Array:
    """bool[J, W] — each *short* job's min(d * n_tasks, W) distinct initial
    probe targets (uniform over the whole DC, ``sparrow.probe_mask``);
    long-job rows are empty (long jobs go to the central scheduler).
    Dense reference view for tests — the transition rule works per edge."""
    short = tasks.job_est < cfg.long_threshold
    return probe_mask(key, cfg, tasks) & short[:, None]


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class EagleLayout:
    """Traced per-window layout for the streaming engine: the short-path
    probe edges (see ``sparrow.ProbeLayout``; long jobs get no edges) plus
    eagle's extras — per-job SSS re-route rotations (host-sampled per
    *global* job id at admission, so carried jobs keep their re-route
    targets across refills) and the central long FIFO.  ``long_fifo``
    lists the window's long task ids in submit order padded with the
    window sentinel ``T``; ``n_long`` (traced — it changes per refill)
    clamps the central head; ``long_window`` is the static central match
    window CL the fifo was padded for.  In streaming mode the SSS and
    central-match stages are always compiled in (a window may gain long
    jobs at any refill)."""

    probes: ProbeLayout   # nested spec'd pytree — checked recursively
    off1: jax.Array = spec("int32[J]")
    off2: jax.Array = spec("int32[J]")
    long_fifo: jax.Array = spec("int32[?]")  # T_cap + long_window ids
    n_long: jax.Array = spec("int32[]")
    long_window: int = dataclasses.field(metadata=dict(static=True))


@spans.span("simx.build")
def make_eagle_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    key: jax.Array,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
    layout: Optional[EagleLayout] = None,
) -> Callable[[EagleState], EagleState]:
    """Build the jittable one-round transition function.

    Round order: fault transitions -> completions (implicit) -> queue
    recycling/compaction -> windowed probe insertion with per-edge SSS
    re-routing -> sticky serve (completed workers continue their previous
    job) -> late binding (idle workers serve their queue heads, orphans
    rescued) -> central long match -> advance the central FIFO head.

    With ``faults``, crashed workers lose their in-flight task (lost long
    tasks roll the central FIFO head back; lost shorts simply re-pend) and
    read busy until recovery — the central scheduler's ground-truth match
    excludes them for free.  SSS additionally bounces probe edges off dead
    workers (the RPC would time out), and a short job whose every live
    reservation died is rescued by any idle worker (see the sparrow rule).
    ``faults=None`` builds the fault-free program; an empty schedule is
    bit-identical to it.

    ``match_fn`` drives the wide central long match ([1, W] rows);
    ``pick_fn`` drives the narrow [W, R] head-of-queue pick — on TPU
    build it with ``default_match_fn(..., block_rows=1)`` (the kernel
    pads each row to ``block_rows * 128`` lanes, so reusing the wide
    match's default tile would inflate the queue rows ~64x).  Both
    default to the jnp reference.
    """
    if match_fn is None:
        match_fn = default_match_fn()
    if pick_fn is None:
        pick_fn = default_match_fn()
    W = cfg.num_workers
    T = tasks.num_tasks
    J = tasks.num_jobs
    R = cfg.short_reserved
    if layout is None:
        check_contiguous(tasks)
        k1, k2, k3 = jax.random.split(key, 3)
        edge_job, edge_worker, edge_end, P, C = build_probe_edges(
            k1, cfg, tasks, short_only=True
        )
        # per-job re-route rotations: stage 1 anywhere, stage 2 short part.
        off1 = jax.random.randint(k2, (J,), 0, W, jnp.int32)
        off2 = jax.random.randint(k3, (J,), 0, R, jnp.int32)
    else:
        if faults is not None:
            raise NotImplementedError(
                "streaming layout does not compose with fault schedules"
            )
        edge_job, edge_worker, edge_end = (
            layout.probes.edge_job,
            layout.probes.edge_worker,
            layout.probes.edge_end,
        )
        C = layout.probes.window
        off1, off2 = layout.off1, layout.off2
    short_job = tasks.job_est < cfg.long_threshold              # bool[J]
    long_task = jnp.concatenate(
        [~short_job[tasks.job], jnp.zeros(1, jnp.bool_)]
    )                                                           # bool[T+1]
    job_pad = jnp.concatenate([tasks.job, jnp.int32([J])])      # int32[T+1]
    dur_pad = jnp.concatenate([tasks.duration, jnp.float32([0.0])])
    job_submit_pad = jnp.concatenate([tasks.job_submit, jnp.float32([jnp.inf])])
    w_row = jnp.arange(W, dtype=jnp.int32)
    j_idx = jnp.arange(J, dtype=jnp.int32)
    job_start, job_end = job_bounds(tasks)
    # central FIFO: long task ids in submit (== task id) order, + CL sentinels
    if layout is None:
        long_ids = np.nonzero(np.asarray(tasks.job_est)[np.asarray(tasks.job)] >= cfg.long_threshold)[0]
        NL = int(long_ids.size)
        CL = min(max(NL, 1), max(W - R, 64))
        long_fifo = jnp.asarray(
            np.concatenate([long_ids, np.full(CL, T)]).astype(np.int32)
        )
        use_sss = bool(NL) or faults is not None
        use_central = bool(NL)
        nl_clamp = NL
    else:
        long_fifo = layout.long_fifo
        CL = layout.long_window
        # a refill may bring long jobs into any window: both long-path
        # stages stay compiled in, clamped by the traced real count
        use_sss = True
        use_central = True
        nl_clamp = layout.n_long
    submit_pad = jnp.concatenate([tasks.submit, jnp.float32([jnp.inf])])
    if faults is not None:
        # task -> central-FIFO position for crash-loss head rollback
        # (short tasks and the T pad map to NL: the min() below ignores them)
        long_pos_np = np.full(T + 1, NL, np.int32)
        long_pos_np[long_ids] = np.arange(NL, dtype=np.int32)
        long_pos = jnp.asarray(long_pos_np)

    def apply_launch(launch, task_pick, start, task_finish, worker_finish, worker_task):
        """The shared launch bookkeeping with eagle's trace constants bound."""
        return rt.apply_launch(
            launch, task_pick, start, dur_pad,
            task_finish, worker_finish, worker_task, T,
        )

    def dispatch(s, t, task_finish0, worker_finish0, free, comp, lost_w):
        # -- 0. crash-loss rollback + ground truth (completions implicit;
        #       the fault/completion stages ran in the runtime) -------------
        del free  # idleness is re-derived after the sticky launches
        with jax.named_scope("simx.eagle.rollback"):
            long_head = s.long_head
            if faults is not None:
                # lost long tasks re-enter the central FIFO: roll the head back
                lt0 = jnp.where(lost_w, s.worker_task, T)
                long_head = jnp.minimum(
                    long_head, jnp.min(long_pos[lt0]) if NL else long_head
                )
            long_here = (worker_finish0 > t) & long_task[s.worker_task]  # bool[W]

        # -- 0b. recycle completed jobs' slots, compact the queues ----------
        with jax.named_scope("simx.eagle.compact"):
            resq, fill = compact_queues(s.resq, task_finish0, t, job_start, job_end)

        # -- 1. windowed probe insertion with per-edge SSS re-routing -------
        with jax.named_scope("simx.eagle.insert"):
            win_j, win_w, lead, ins, lagged = probe_window_slice(
                edge_job, edge_worker, s.probe_head, C, job_submit_pad, t
            )
            if use_sss:
                if faults is not None:
                    # SSS also bounces probes off dead workers (the RPC times out)
                    sss_reject = long_here | worker_dead(faults, t)
                else:
                    sss_reject = long_here
                wj = jnp.clip(win_j, 0, max(J - 1, 0))
                rej0 = ins & sss_reject[jnp.clip(win_w, 0, W - 1)]
                w1 = jnp.where(rej0, (win_w + off1[wj]) % W, win_w)
                rej1 = rej0 & sss_reject[w1]
                wfin = jnp.where(rej1, (w1 + off2[wj]) % R, w1)
                n_rej0 = jnp.sum(rej0, dtype=jnp.int32)
                n_rej1 = jnp.sum(rej1, dtype=jnp.int32)
            else:  # no long jobs in the trace: SSS machinery compiles out
                wfin = win_w
                n_rej0 = n_rej1 = jnp.int32(0)
            resq, n_over = insert_probes(resq, fill, wfin, win_j, ins)
            head = s.probe_head + lead
            # see the sparrow rule: saturated windows make probe lag observable
            lag = s.probe_lag + lagged.astype(jnp.int32)
            probes = s.probes + lead + n_rej0 + n_rej1
            messages = s.messages + lead + 2 * (n_rej0 + n_rej1)    # reject + resend

        # -- 2. sticky batch draining: completed workers keep their job -----
        with jax.named_scope("simx.eagle.drain"):
            pend_task = jnp.isinf(task_finish0) & (tasks.submit <= t)
            c, base, pending = job_counts(pend_task, job_start, job_end)
            prev_job = job_pad[s.worker_task]                       # int32[W], J=none
            pend_prev = jnp.append(pending, 0)[prev_job]
            sticky_pick = jnp.where(comp & (pend_prev > 0), prev_job, J)
            launch1, task1 = late_bind(sticky_pick, c, base, pending)
            # the worker already holds the job's spec: no extra hops
            task_finish, worker_finish, worker_task = apply_launch(
                launch1, task1, t, task_finish0, worker_finish0, s.worker_task
            )

        # -- 3. late binding: idle workers serve their queue heads ----------
        with jax.named_scope("simx.eagle.bind"):
            pend_task = jnp.isinf(task_finish) & (tasks.submit <= t)
            c, base, pending = job_counts(pend_task, job_start, job_end)
            idle = worker_finish <= t
            # orphan rescue (see the sparrow rule): a pending short job with no
            # live reservation anywhere may be served by any idle worker
            dead = worker_dead(faults, t) if faults is not None else None
            job_pick, reserved, at_cap = queue_heads(
                resq, pending, pick_fn, idle=idle, dead=dead
            )
            orphan = short_job & (edge_end <= head) & (pending > 0) & ~reserved
            rescue = jnp.min(jnp.where(orphan, j_idx, J))
            job_pick = jnp.where(idle, jnp.minimum(job_pick, rescue), J)
            launch2, task2 = late_bind(job_pick, c, base, pending)
            start = t + 3 * cfg.hop  # get-task RPC round trip + launch
            task_finish, worker_finish, worker_task = apply_launch(
                launch2, task2, start, task_finish, worker_finish, worker_task
            )
            messages = messages + 2 * jnp.sum(launch2, dtype=jnp.int32)

            n_launch = (
                jnp.sum(launch1, dtype=jnp.int32) + jnp.sum(launch2, dtype=jnp.int32)
            )

        # -- 4. central scheduler: queued long window -> free long partition
        with jax.named_scope("simx.eagle.central"):
            if use_central:
                wtask = jax.lax.dynamic_slice(long_fifo, (long_head,), (CL,))
                wsub = submit_pad[jnp.minimum(wtask, T)]
                wsub = jnp.where(wtask >= T, jnp.inf, wsub)
                fpad = rt.finish_pad(task_finish)
                launched = rt.window_launched(fpad, wtask, T)       # bool[CL]
                queued = ~launched & (wsub <= t)
                nq = jnp.sum(queued, dtype=jnp.int32)
                # sticky launches punch holes mid-window: sort queued positions
                # ahead of the CL sentinels to recover FIFO order
                fifo = rt.sorted_fifo(queued, CL)
                avail = ((worker_finish <= t) & (w_row >= R))[None, :]
                ranks = match_fn(avail, nq[None])[0]                # int32[W]
                sel_task = rt.select_from_window(ranks, fifo, wtask, T)
                launch3 = sel_task < T
                task_finish, worker_finish, worker_task = apply_launch(
                    launch3, sel_task, start, task_finish, worker_finish, worker_task
                )
                messages = messages + jnp.sum(launch3, dtype=jnp.int32)
                n_launch = n_launch + jnp.sum(launch3, dtype=jnp.int32)
                # advance the head past the launched prefix
                fpad2 = rt.finish_pad(task_finish)
                launched2 = rt.window_launched(fpad2, wtask, T)
                long_head = jnp.minimum(
                    long_head + rt.launched_lead(launched2), nl_clamp
                )

        upd = dict(
            task_finish=task_finish,
            worker_finish=worker_finish,
            worker_task=worker_task,
            resq=resq,
            probe_head=head,
            res_overflow=s.res_overflow + n_over,
            probe_lag=lag,
            long_head=long_head,
            messages=messages,
            probes=probes,
        )
        if telemetry:
            with jax.named_scope("simx.telemetry"):
                upd["telemetry"] = dict(
                    launches=n_launch, sss_rejections=n_rej0 + n_rej1,
                    full_width_rounds=at_cap,
                )
        if provenance:
            with jax.named_scope("simx.provenance"):
                # attempt = a scheduler acted on the task's job this round:
                # short-path probes inserted (or orphan-rescued), or the long
                # task sat in the central scheduler's queued match window.
                # Sticky launches are or-ed in by the runtime's launch latch.
                # authority = the job's home distributed scheduler for short
                # jobs (job % num_gms), entity ``num_gms`` for the central
                # long-path scheduler.
                att_j = (
                    jnp.zeros(J + 1, jnp.bool_)
                    .at[jnp.where(ins, win_j, J)]
                    .set(True, mode="drop")
                )
                att_j = att_j.at[:-1].max(orphan)
                attempt = att_j[:-1][tasks.job]
                if use_central:
                    attempt = attempt | (
                        jnp.zeros(T, jnp.bool_)
                        .at[jnp.where(queued, wtask, T)]
                        .set(True, mode="drop")
                    )
                aj = job_pad[jnp.minimum(worker_task, T)]
                authority = jnp.where(
                    long_task[jnp.minimum(worker_task, T)],
                    jnp.int32(cfg.num_gms),
                    (jnp.minimum(aj, J - 1) % cfg.num_gms).astype(jnp.int32),
                )
                upd["provenance"] = dict(attempt=attempt, authority=authority)
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
    pick_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
) -> EagleState:
    """Run exactly ``num_rounds`` rounds from an idle DC (vmap-able in seed
    and in the submit-time arrays)."""
    return rt.simulate_fixed(
        "eagle", cfg, tasks, seed, num_rounds,
        match_fn=match_fn, pick_fn=pick_fn, faults=faults,
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
) -> Callable[[EagleState], EagleState]:
    return make_eagle_step(
        cfg, tasks, key, match_fn, pick_fn, faults=faults, telemetry=telemetry,
        provenance=provenance,
    )


RULE = rt.register_rule(
    rt.Rule(
        name="eagle",
        init=lambda cfg, tasks: init_eagle_state(cfg, tasks),
        build_step=_build_step,
        has_queues=True,
    )
)
