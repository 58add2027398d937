"""Pigeon transition rule for the simx round-stepped backend.

Federated two-layer scheduling (paper §2.2.4) over dense per-group arrays:

  * **Static distribution** — the event backend's distributors spread each
    job's tasks round-robin (task by task, persistent per-distributor
    counters, jobs round-robin over distributors).  That mapping depends
    only on the trace, so the task -> group assignment is precomputed
    exactly, in numpy, at step-build time.
  * **Per-group FIFOs** — each group holds a high-priority (short job) and
    a low-priority (long job) FIFO.  Tasks arrive in submit order, groups
    launch strictly from the FIFO head, so each queue is a windowed head
    pointer over a compact per-group task layout (megha's window trick,
    without the failure/retry machinery: coordinators have current
    knowledge of their own group, so every proposal launches).
  * **Reserved workers** — the first ``reserved_per_group`` workers of each
    group serve high-priority tasks only; high tasks prefer unreserved
    workers, low tasks never touch reserved ones.
  * **WFQ** — unreserved capacity is split between the two queues by a
    closed-form weighted-fair-queuing allocation: per ``wfq_weight``
    high-priority launches, one low-priority launch, with the carried
    ``since_low`` counter preserving the pattern phase across rounds.
    Within a round all launches share one start time, so only the
    high/low *counts* matter, not their interleaving — the closed form is
    exact whenever one queue drains and a faithful ratio otherwise (the
    group-master quantization note in ``engine`` spells this out).

The key pathology Megha fixes is preserved: a task assigned to a group
never migrates, so it queues even when other groups have idle workers.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx import runtime as rt, spans
from repro.simx.faults import FaultSchedule
from repro.simx.runtime import MatchFn, default_match_fn
from repro.simx.state import (
    PigeonState,
    SimxConfig,
    TaskArrays,
    init_pigeon_state,
    spec,
)


def task_groups(cfg: SimxConfig, tasks: TaskArrays) -> np.ndarray:
    """int[T] — the group each task is distributed to, replicating the
    event backend's persistent per-distributor round-robin exactly."""
    NG, D = cfg.num_groups, cfg.num_distributors
    ntasks = np.asarray(tasks.job_ntasks)
    rr = np.arange(D, dtype=np.int64)  # each distributor decorrelates its start
    out = np.empty(tasks.num_tasks, np.int32)
    k = 0
    for p in range(tasks.num_jobs):
        d = p % D
        c = int(ntasks[p])
        out[k : k + c] = (rr[d] + np.arange(c)) % NG
        rr[d] += c
        k += c
    return out


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class PigeonLayout:
    """Traced per-window FIFO layout for the streaming engine.

    Rows list each group's window-task ids per priority class in submit
    order (the group assignment comes from the *persistent* host-side
    distributor round-robin counters, so a refill never re-distributes a
    task), padded with the window sentinel ``T`` — both fifos are padded
    by the static window C = max(S, 1).  ``len_high``/``len_low`` hold
    the real per-group row lengths for the head clamps (traced: they
    change every refill).
    """

    high_fifo: jax.Array = spec("int32[NG, ?]")  # rows: Lh_cap + C
    low_fifo: jax.Array = spec("int32[NG, ?]")   # rows: Ll_cap + C
    len_high: jax.Array = spec("int32[NG]")
    len_low: jax.Array = spec("int32[NG]")


@spans.span("simx.build")
def make_pigeon_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    match_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
    layout: Optional[PigeonLayout] = None,
) -> Callable[[PigeonState], PigeonState]:
    """Build the jittable one-round transition function.

    Round order: completions (implicit via ``worker_finish``) -> WFQ split
    of each group's free unreserved workers between its high/low queue
    heads -> high overflow onto reserved workers -> launch + head advance.

    With ``faults``, crashed workers lose their in-flight task (the group's
    high/low head rolls back so the FIFO re-examines it) and read busy
    until recovery, which shrinks the group's capacity — tasks can NOT
    migrate groups (the pathology megha fixes), so a decimated group
    queues until its workers return.  Because rolled-back windows contain
    already-launched tasks, the fault build swaps the submitted-prefix
    queue count for an explicit unlaunched mask + sorted FIFO positions
    and advances heads past the launched prefix (megha's window idiom);
    without rollbacks both forms coincide, so an empty schedule stays
    bit-identical to the ``faults=None`` program.
    """
    if match_fn is None:
        match_fn = default_match_fn()
    W = cfg.num_workers
    T = tasks.num_tasks
    NG = cfg.num_groups
    weight = cfg.wfq_weight
    # -- worker grid [NG, S]: contiguous ranges, last group absorbs the
    #    remainder, pad slots get the W sentinel (dropped by scatters)
    sizes = np.full(NG, cfg.group_size, np.int64)
    sizes[-1] = W - (NG - 1) * cfg.group_size
    S = int(sizes.max())
    wg_np = np.full((NG, S), W, np.int64)
    rsv_np = np.zeros((NG, S), bool)
    for g in range(NG):
        base = g * cfg.group_size
        wg_np[g, : sizes[g]] = base + np.arange(sizes[g])
        rsv_np[g, : min(cfg.reserved_per_group, sizes[g])] = True
    wg = jnp.asarray(wg_np, jnp.int32)
    reserved = jnp.asarray(rsv_np)
    if provenance:
        # static worker -> group map (provenance authority track)
        wgrp_np = np.zeros(W, np.int32)
        for g in range(NG):
            wgrp_np[wg_np[g][wg_np[g] < W]] = g
        worker_group = jnp.asarray(wgrp_np)
    C = max(S, 1)  # window width: a group launches at most S tasks per round
    if layout is None:
        # -- exact static task -> group distribution, split by priority class
        gt = task_groups(cfg, tasks)
        high_task = np.asarray(tasks.job_est)[np.asarray(tasks.job)] < cfg.long_threshold

        task_pos_np = np.zeros(T + 1, np.int32)  # task -> position in its FIFO

        def class_layout(mask: np.ndarray) -> jax.Array:
            length = int(np.max(np.bincount(gt[mask], minlength=NG))) if mask.any() else 0
            rows = np.full((NG, length + C), T, np.int32)
            for g in range(NG):
                mine = np.nonzero(mask & (gt == g))[0]
                rows[g, : mine.size] = mine
                task_pos_np[mine] = np.arange(mine.size, dtype=np.int32)
            return jnp.asarray(rows)

        high_fifo = class_layout(high_task)  # int32[NG, Lh+C], ascending = FIFO
        low_fifo = class_layout(~high_task)  # int32[NG, Ll+C]
        len_h = high_fifo.shape[1] - C
        len_l = low_fifo.shape[1] - C
    else:
        if faults is not None:
            raise NotImplementedError(
                "streaming layout does not compose with fault schedules"
            )
        high_fifo, low_fifo = layout.high_fifo, layout.low_fifo
        len_h, len_l = layout.len_high, layout.len_low
    submit_pad = jnp.concatenate([tasks.submit, jnp.float32([jnp.inf])])
    dur_pad = jnp.concatenate([tasks.duration, jnp.float32([0.0])])
    if faults is not None:
        # task -> (group, FIFO position, class) for crash-loss head rollback;
        # the T pad routes to the out-of-bounds group NG (scatter-dropped)
        task_pos_pad = jnp.asarray(task_pos_np)
        grp_pad = jnp.concatenate([jnp.asarray(gt, jnp.int32), jnp.int32([NG])])
        high_pad = jnp.concatenate(
            [jnp.asarray(high_task), jnp.zeros(1, jnp.bool_)]
        )

    def window(fifo, heads, t):
        """Window task ids + queued counts.  Launches are strictly FIFO and
        the head fully advances every round, so the window never contains a
        launched task and 'queued' is just the submitted prefix."""
        wtask = rt.slice_rows(fifo, heads, C)                   # int32[NG,C]
        wsub = jnp.where(wtask >= T, jnp.inf, submit_pad[jnp.minimum(wtask, T)])
        return wtask, jnp.sum(wsub <= t, axis=1, dtype=jnp.int32)

    def window_fault(fifo, heads, t, task_finish):
        """Fault-mode window: a rolled-back head re-examines launched tasks,
        so 'queued' needs the explicit unlaunched mask and rank -> task
        goes through sorted queued positions (megha's FIFO recovery)."""
        wtask = rt.slice_rows(fifo, heads, C)                   # int32[NG,C]
        wsub = jnp.where(wtask >= T, jnp.inf, submit_pad[jnp.minimum(wtask, T)])
        fpad = rt.finish_pad(task_finish)
        launched = ~jnp.isinf(fpad[wtask])                      # pad: False
        queued = ~launched & (wsub <= t)
        return wtask, jnp.sum(queued, axis=1, dtype=jnp.int32), rt.sorted_fifo(queued, C)

    def dispatch(s, t, task_finish0, worker_finish0, free_w, comp, lost_w):
        # -- 0. crash-loss rollback (fault stage ran in the runtime) --------
        del comp  # completions stay implicit in the group capacity gather
        with jax.named_scope("simx.pigeon.rollback"):
            high_head0, low_head0 = s.high_head, s.low_head
            if faults is not None:
                # re-enqueue lost tasks: roll the owning group's class FIFO back
                lt0 = jnp.where(lost_w, s.worker_task, T)
                g0, p0, hi0 = grp_pad[lt0], task_pos_pad[lt0], high_pad[lt0]
                high_head0 = high_head0.at[jnp.where(hi0, g0, NG)].min(
                    p0, mode="drop"
                )
                low_head0 = low_head0.at[jnp.where(hi0, NG, g0)].min(
                    p0, mode="drop"
                )

        # -- 1. free capacity per group (the runtime's completion stage,
        #       gathered into the [NG, S] group grid; a crashed worker holds
        #       its recovery time, shrinking group capacity; pads read busy)
        with jax.named_scope("simx.pigeon.wfq"):
            free = jnp.concatenate([free_w, jnp.zeros(1, jnp.bool_)])[wg]  # [NG,S]
            free_u = free & ~reserved
            free_r = free & reserved
            nfu = jnp.sum(free_u, axis=1, dtype=jnp.int32)             # int32[NG]
            nfr = jnp.sum(free_r, axis=1, dtype=jnp.int32)

            # -- 2. queued counts + WFQ split of unreserved capacity --------
            if faults is None:
                wh, qh = window(high_fifo, high_head0, t)
                wl, ql = window(low_fifo, low_head0, t)
            else:
                wh, qh, fifo_h = window_fault(high_fifo, high_head0, t, task_finish0)
                wl, ql, fifo_l = window_fault(low_fifo, low_head0, t, task_finish0)
            total_u = jnp.minimum(nfu, qh + ql)
            lead = jnp.maximum(0, weight - s.since_low)  # highs before first low
            low_wfq = jnp.where(
                total_u > lead, 1 + (total_u - lead - 1) // (weight + 1), 0
            )
            n_low = jnp.clip(
                low_wfq, jnp.maximum(total_u - qh, 0), jnp.minimum(ql, total_u)
            )
            n_high_u = total_u - n_low
            n_high_r = jnp.minimum(qh - n_high_u, nfr)  # overflow onto reserved
            since_low = jnp.maximum(0, s.since_low + n_high_u - weight * n_low)

        # -- 3. rank-and-select free workers, map ranks to FIFO positions ---
        with jax.named_scope("simx.pigeon.match"):
            ranks_u = match_fn(free_u, n_high_u + n_low)               # int32[NG,S]
            ranks_r = match_fn(free_r, n_high_r)
            if faults is None:
                # no holes: the r-th queued task sits at window position r
                ru = jnp.clip(ranks_u, 0, C - 1)
                task_u = jnp.where(
                    ranks_u < 0,
                    T,
                    jnp.where(
                        ranks_u < n_high_u[:, None],
                        jnp.take_along_axis(wh, ru, axis=1),
                        jnp.take_along_axis(
                            wl, jnp.clip(ranks_u - n_high_u[:, None], 0, C - 1), axis=1
                        ),
                    ),
                )
                task_r = jnp.where(
                    ranks_r < 0,
                    T,
                    jnp.take_along_axis(
                        wh, jnp.clip(n_high_u[:, None] + ranks_r, 0, C - 1), axis=1
                    ),
                )
            else:
                # rank -> sorted queued position -> window task id
                pos_uh = jnp.take_along_axis(
                    fifo_h, jnp.clip(ranks_u, 0, C - 1), axis=1
                )
                pos_ul = jnp.take_along_axis(
                    fifo_l, jnp.clip(ranks_u - n_high_u[:, None], 0, C - 1), axis=1
                )
                task_u = jnp.where(
                    ranks_u < 0,
                    T,
                    jnp.where(
                        ranks_u < n_high_u[:, None],
                        jnp.take_along_axis(wh, jnp.clip(pos_uh, 0, C - 1), axis=1),
                        jnp.take_along_axis(wl, jnp.clip(pos_ul, 0, C - 1), axis=1),
                    ),
                )
                pos_r = jnp.take_along_axis(
                    fifo_h, jnp.clip(n_high_u[:, None] + ranks_r, 0, C - 1), axis=1
                )
                task_r = jnp.where(
                    ranks_r < 0,
                    T,
                    jnp.take_along_axis(wh, jnp.clip(pos_r, 0, C - 1), axis=1),
                )
            task_g = jnp.minimum(task_u, task_r)  # disjoint slots: one is T
            launch = task_g < T                                         # [NG,S]

        # -- 4. launch: client->distributor->coordinator->worker = 3 hops ---
        with jax.named_scope("simx.pigeon.launch"):
            start = t + 3 * cfg.hop
            fin = start + dur_pad[jnp.minimum(task_g, T)]
            task_finish = task_finish0.at[jnp.where(launch, task_g, T)].set(
                fin, mode="drop"
            )
            worker_finish = worker_finish0.at[jnp.where(launch, wg, W)].set(
                fin, mode="drop"
            )
            worker_task = s.worker_task.at[jnp.where(launch, wg, W)].set(
                task_g, mode="drop"
            )
            # messages: one distributor->coordinator per arriving task, one
            # coordinator->worker per launch
            arrived = jnp.sum(
                (tasks.submit > t - cfg.dt) & (tasks.submit <= t), dtype=jnp.int32
            )
            messages = (
                s.messages + arrived + jnp.sum(launch, dtype=jnp.int32)
            )

            # -- 5. head advance --------------------------------------------
            if faults is None:
                # strict FIFO launches: advance by the launch counts
                high_head = jnp.minimum(high_head0 + n_high_u + n_high_r, len_h)
                low_head = jnp.minimum(low_head0 + n_low, len_l)
            else:
                # rolled-back windows have holes: advance past the launched
                # prefix instead (equals the counts whenever there are none).
                # Pads read NOT launched here (unlike ``rt.window_launched``):
                # the head stops at the real tail instead of running through
                # the pad slots.
                fpad2 = rt.finish_pad(task_finish)
                lead_h = rt.launched_lead(~jnp.isinf(fpad2[wh]))
                lead_l = rt.launched_lead(~jnp.isinf(fpad2[wl]))
                high_head = jnp.minimum(high_head0 + lead_h, len_h)
                low_head = jnp.minimum(low_head0 + lead_l, len_l)

            upd = dict(
                task_finish=task_finish,
                worker_finish=worker_finish,
                worker_task=worker_task,
                high_head=high_head,
                low_head=low_head,
                since_low=since_low,
                messages=messages,
            )
        if telemetry:
            with jax.named_scope("simx.telemetry"):
                upd["telemetry"] = dict(
                    launches=jnp.sum(launch, dtype=jnp.int32),
                    reserve_hits=jnp.sum(n_high_r, dtype=jnp.int32),
                )
        if provenance:
            with jax.named_scope("simx.provenance"):
                # attempt = the task sat in its group coordinator's queued
                # window this round (the submitted prefix — or the explicit
                # queued mask under fault rollbacks).  authority = the group
                # coordinator, which is static per worker.
                col = jnp.arange(C, dtype=jnp.int32)[None, :]
                if faults is None:
                    att_h = col < qh[:, None]
                    att_l = col < ql[:, None]
                else:
                    fpad_a = rt.finish_pad(task_finish0)
                    att_h = jnp.isinf(fpad_a[wh]) & (
                        jnp.where(wh >= T, jnp.inf, submit_pad[jnp.minimum(wh, T)])
                        <= t
                    )
                    att_l = jnp.isinf(fpad_a[wl]) & (
                        jnp.where(wl >= T, jnp.inf, submit_pad[jnp.minimum(wl, T)])
                        <= t
                    )
                attempt = (
                    jnp.zeros(T, jnp.bool_)
                    .at[jnp.where(att_h, wh, T)]
                    .set(True, mode="drop")
                    .at[jnp.where(att_l, wl, T)]
                    .set(True, mode="drop")
                )
                upd["provenance"] = dict(attempt=attempt, authority=worker_group)
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
) -> PigeonState:
    """Run exactly ``num_rounds`` rounds from an idle DC.  Pigeon's
    transition is deterministic given the trace; ``seed`` is accepted for
    signature parity with the other schedulers (vmap-able all the same)."""
    return rt.simulate_fixed(
        "pigeon", cfg, tasks, seed, num_rounds, match_fn=match_fn, faults=faults
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
) -> Callable[[PigeonState], PigeonState]:
    del key, pick_fn  # static round-robin distribution, no queues
    return make_pigeon_step(
        cfg, tasks, match_fn, faults=faults, telemetry=telemetry,
        provenance=provenance,
    )


RULE = rt.register_rule(
    rt.Rule(
        name="pigeon",
        init=lambda cfg, tasks: init_pigeon_state(cfg, tasks.num_tasks),
        build_step=_build_step,
    )
)
