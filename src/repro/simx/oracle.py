"""Omniscient centralized oracle — the global-knowledge lower bound.

The paper's thesis (and Pronto's framing in PAPERS.md) is that parallel
schedulers with *partial* knowledge pay avoidable queuing delay; this rule
quantifies "avoidable".  One centralized scheduler with perfect, instant
knowledge of every worker serves one global FIFO: each round every queued
task in the head window is matched onto the actually-free workers through
the same rank-and-select primitive, with the same launch hop costs as the
real schedulers.  No stale views (megha), no sampling (sparrow), no
partitions (eagle), no static groups (pigeon) — the only delays left are
genuine capacity waits, network hops, and the shared ``dt`` round
quantization.  The gap between any scheduler's p50/p95 job delay and the
oracle's on the same trace is therefore its partial-knowledge cost — the
paper's Fig. 2 argument, measured (``bench_simx.py`` reports it as the
``simx_oracle_gap`` row).

Being a ~130-line ``Rule`` on the shared round-stage runtime
(``repro.simx.runtime``), this is also the proof that adding a scheduler
no longer means re-implementing the round machinery: the dispatch stage
below is the entire scheduler.

Under faults the oracle plays by the same rules as everyone else: crashed
workers lose their in-flight task (re-pended via a FIFO-head rollback —
task ids ARE global FIFO positions) and read busy until recovery; perfect
knowledge means the oracle simply never *proposes* onto a dead worker.
GM outages don't apply (there are no GMs to take down).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx import runtime as rt, spans
from repro.simx.faults import FaultSchedule
from repro.simx.runtime import MatchFn, default_match_fn
from repro.simx.state import OracleState, SimxConfig, TaskArrays, init_oracle_state


@spans.span("simx.build")
def make_oracle_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    match_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
) -> Callable[[OracleState], OracleState]:
    """Build the jittable one-round transition function.

    The global FIFO is the task-id order itself (``export_workload`` sorts
    tasks by job submit time), so the queue is just a head pointer over
    ``arange(T)`` — megha's window idiom with G = 1 and no failure/retry
    machinery: the oracle matches against ground truth, so every proposal
    launches.  The window is at least W wide (capped at T), so a single
    round can fill the entire datacenter and the cap never binds.
    """
    if match_fn is None:
        match_fn = default_match_fn()
    T = tasks.num_tasks
    W = cfg.num_workers
    C = int(min(max(W, 64), max(T, 1)))
    # the FIFO: task ids in submit order, padded so the window never
    # slices out of bounds at head == T
    fifo = jnp.asarray(
        np.concatenate([np.arange(T), np.full(C, T)]).astype(np.int32)
    )
    submit_pad = jnp.concatenate([tasks.submit, jnp.float32([jnp.inf])])
    dur_pad = jnp.concatenate([tasks.duration, jnp.float32([0.0])])

    def dispatch(s, t, task_finish0, worker_finish0, free, comp, lost_w):
        del comp
        # -- 0. crash-loss rollback: a lost task's id is its FIFO position -
        with jax.named_scope("simx.oracle.rollback"):
            head0 = s.head
            if faults is not None:
                lost_t = jnp.where(lost_w, s.worker_task, T)
                head0 = jnp.minimum(head0, jnp.min(lost_t))

        # -- 1. queued window (holes possible after a rollback) -------------
        with jax.named_scope("simx.oracle.window"):
            wtask = jax.lax.dynamic_slice(fifo, (head0,), (C,))
            wsub = jnp.where(wtask >= T, jnp.inf, submit_pad[jnp.minimum(wtask, T)])
            fpad = rt.finish_pad(task_finish0)
            launched = rt.window_launched(fpad, wtask, T)             # bool[C]
            queued = ~launched & (wsub <= t)
            nq = jnp.sum(queued, dtype=jnp.int32)
            fifo_pos = rt.sorted_fifo(queued, C)

        # -- 2. perfect match: FIFO ranks onto actually-free workers --------
        with jax.named_scope("simx.oracle.match"):
            ranks = match_fn(free[None, :], nq[None])[0]              # int32[W]
            sel_task = rt.select_from_window(ranks, fifo_pos, wtask, T)
            launch = sel_task < T

        # -- 3. launch: same hop costs as the real schedulers ---------------
        with jax.named_scope("simx.oracle.launch"):
            task_finish, worker_finish, worker_task = rt.apply_launch(
                launch, sel_task, t + 3 * cfg.hop, dur_pad,
                task_finish0, worker_finish0, s.worker_task, T,
            )
            messages = s.messages + jnp.sum(launch, dtype=jnp.int32)

            # -- 4. advance the head past the launched prefix ---------------
            fpad2 = rt.finish_pad(task_finish)
            launched2 = rt.window_launched(fpad2, wtask, T)
            head = jnp.minimum(head0 + rt.launched_lead(launched2), T)

            upd = dict(
                task_finish=task_finish,
                worker_finish=worker_finish,
                worker_task=worker_task,
                head=head,
                messages=messages,
            )
        if telemetry:
            with jax.named_scope("simx.telemetry"):
                upd["telemetry"] = dict(launches=jnp.sum(launch, dtype=jnp.int32))
        if provenance:
            with jax.named_scope("simx.provenance"):
                # attempt = the whole queued window (every queued task in it
                # was ranked against the free set); authority = the single
                # omniscient scheduler, entity 0
                attempt = (
                    jnp.zeros(T, jnp.bool_)
                    .at[jnp.where(queued, wtask, T)]
                    .set(True, mode="drop")
                )
                upd["provenance"] = dict(
                    attempt=attempt, authority=jnp.zeros(W, jnp.int32)
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
) -> OracleState:
    """Run exactly ``num_rounds`` rounds from an idle DC.  The oracle is
    deterministic given the trace; ``seed`` is signature parity."""
    return rt.simulate_fixed(
        "oracle", cfg, tasks, seed, num_rounds, match_fn=match_fn, faults=faults
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
) -> Callable[[OracleState], OracleState]:
    del key, pick_fn  # deterministic, no reservation queues
    return make_oracle_step(
        cfg, tasks, match_fn, faults=faults, telemetry=telemetry,
        provenance=provenance,
    )


RULE = rt.register_rule(
    rt.Rule(
        name="oracle",
        init=lambda cfg, tasks: init_oracle_state(cfg, tasks.num_tasks),
        build_step=_build_step,
    )
)
