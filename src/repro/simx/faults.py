"""Round-space fault injection for the simx backend (paper §3.5, Fig. 4).

The event backend injects faults imperatively (``fail_gm`` / ``recover_gm``
/ ``fail_worker`` callbacks on the loop); simx instead *compiles the fault
schedule into the transition rule*: a ``FaultSchedule`` is a pytree of
dense per-worker / per-GM crash and recovery times that every round's step
function masks against, so fault studies jit, scan, and ``vmap`` over a
whole severity grid exactly like a Fig. 2 load grid (``sweep.fig4_sweep``).

The crash transition itself runs as stage 1 of the shared round pipeline
(``runtime.fault_stage`` inside ``runtime.compose_step``), so every
registered rule — including ones added later — inherits it; rules only
supply their FIFO-head rollback from the returned loss mask.  Semantics
shared by every scheduler (megha, sparrow, eagle, pigeon, oracle):

  * a worker is **down** during ``[worker_down, worker_up)``.  At the crash
    round its in-flight task (if any) is *lost*: the task returns to the
    pending pool (``task_finish`` reset to inf) and the owning queue's head
    pointer rolls back so the FIFO re-examines it; the ``lost`` counter
    increments.  While down the worker reads as busy-until-recovery
    (``worker_finish = worker_up``), so every scheduler's ground-truth
    free test excludes it with no extra masking — and megha's *stale GM
    views* keep proposing onto it until a heartbeat / piggyback repairs
    them, which is exactly the paper's inconsistency-repair accounting.
  * ``worker_up == worker_down`` models the event backend's instant-restart
    ``fail_worker`` (the LM restarts the worker and re-runs the lost task);
    the restart lands at the next round boundary (<= ``dt`` quantization).
  * megha GMs are **down** during ``[gm_down, gm_up)``.  A down GM stops
    matching; each round its queue (arrivals included — round-synchronous
    execution makes arrivals and queued tasks indistinguishable) is adopted
    by a live GM chosen round-robin by round index, which matches it
    against the adopter's own eventually-consistent view — the round-space
    analog of rerouting arrivals to live GMs (§3.5).  On recovery the GM's
    view is reset from LM ground truth (``rebuild_from_heartbeats``).
  * ``hb_extra_rounds`` stretches megha's heartbeat period (a heartbeat-
    delay perturbation); the other schedulers have no heartbeats.

The **empty schedule is a no-op by construction**: every fault transition
is a masked update whose mask is identically false (or an identity gather)
when all fault times are ``inf``, so results are bit-identical to the
fault-free path — ``tests/test_simx_faults.py`` pins this bitwise.

``FaultPlan`` is the backend-neutral description: a list of worker
failures and GM outages in simulated seconds that either compiles to a
``FaultSchedule`` (simx) or installs the imperative hooks on the event
loop (events backend), giving ``run_simulation(..., faults=...)`` one
fault API across both backends.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx.state import spec


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class FaultSchedule:
    """Dense fault schedule (all times in simulated seconds; inf = never).

    Leaves batch: a schedule whose arrays carry a leading severity axis
    vmaps through ``simulate_fixed`` like any other traced input.
    """

    worker_down: jax.Array = spec("float32[W]")  # crash time
    worker_up: jax.Array = spec("float32[W]")    # recovery time (>= down)
    gm_down: jax.Array = spec("float32[G]")  # GM down-window start (megha)
    gm_up: jax.Array = spec("float32[G]")    # GM down-window end
    hb_extra_rounds: jax.Array = spec("int32[]")  # heartbeat-delay
                                # perturbation, rounds added to the period

    def replace(self, **kw) -> "FaultSchedule":
        return dataclasses.replace(self, **kw)


def empty_schedule(num_workers: int, num_gms: int = 8) -> FaultSchedule:
    """The no-op schedule: nothing ever fails (bit-identical results)."""
    return FaultSchedule(
        worker_down=jnp.full(num_workers, jnp.inf, jnp.float32),
        worker_up=jnp.full(num_workers, jnp.inf, jnp.float32),
        gm_down=jnp.full(num_gms, jnp.inf, jnp.float32),
        gm_up=jnp.full(num_gms, jnp.inf, jnp.float32),
        hb_extra_rounds=jnp.int32(0),
    )


def is_empty(fs: FaultSchedule) -> bool:
    """Host-side check (not jittable): does this schedule inject nothing?"""
    return bool(
        jnp.all(jnp.isinf(fs.worker_down))
        and jnp.all(jnp.isinf(fs.gm_down))
        and jnp.all(fs.hb_extra_rounds == 0)
    )


# ---------------------------------------------------------------------------
# masked transitions shared by every rule's step function
# ---------------------------------------------------------------------------


def worker_dead(fs: FaultSchedule, t: jax.Array) -> jax.Array:
    """bool[W] — down at round-start time ``t`` (instant restarts never are)."""
    return (fs.worker_down <= t) & (t < fs.worker_up)


def apply_worker_faults(
    fs: FaultSchedule,
    t: jax.Array,
    dt: float,
    task_finish: jax.Array,
    worker_finish: jax.Array,
    worker_task: jax.Array,
    num_tasks: int,
):
    """The round-start crash transition shared by every rule (stage 1
    of ``runtime.compose_step``).

    Workers whose crash time fell inside the round window just ended lose
    their in-flight task (re-pended) and read busy until their recovery
    time.  Returns ``(task_finish, worker_finish, lost_w bool[W], n_lost)``
    — callers roll back their FIFO heads from ``lost_w`` and accumulate
    ``n_lost`` into the state's ``lost`` counter.  With an empty schedule
    every mask is false and the arrays pass through bit-identically.
    """
    crashed = (fs.worker_down <= t) & (fs.worker_down > t - dt)  # bool[W]
    lost_w = crashed & (worker_finish > t)
    lost_t = jnp.where(lost_w, worker_task, num_tasks)           # T = none
    task_finish = task_finish.at[lost_t].set(jnp.inf, mode="drop")
    worker_finish = jnp.where(crashed, fs.worker_up, worker_finish)
    return task_finish, worker_finish, lost_w, jnp.sum(lost_w, dtype=jnp.int32)


def jobs_with_reservation(
    resq: jax.Array, num_jobs: int, dead: jax.Array | None = None
) -> jax.Array:
    """bool[J] — jobs holding at least one reservation-queue entry (on a
    currently-live worker when ``dead`` is given).

    The queue-walking replacement for the dense-mask orphan test
    ``any(probes & ~dead[None, :], axis=1)``: one scatter-max over the
    ``int32[W, R]`` per-worker queues (J = empty sentinel, dropped as
    out-of-bounds) instead of a [J, W] reduction.  Sparrow and eagle use
    it for orphan rescue — a pending job with no live entry anywhere
    (every probed worker down, or every probe dropped on a full queue) is
    temporarily servable by any idle worker.  The scatter runs in int32:
    a ``pred`` scatter-max gives the same answer but compiles several
    times slower on the TPU, and runs slower there.
    """
    exists = resq < num_jobs
    if dead is not None:
        exists = exists & ~dead[:, None]
    held = (
        jnp.zeros(num_jobs, jnp.int32)
        .at[resq.ravel()]
        .max(exists.ravel().astype(jnp.int32), mode="drop")
    )
    return held > 0


def gm_down_mask(fs: FaultSchedule, t: jax.Array) -> jax.Array:
    """bool[G] — GMs inside their down window at time ``t``."""
    return (fs.gm_down <= t) & (t < fs.gm_up)


def gm_recovered_now(fs: FaultSchedule, t: jax.Array, dt: float) -> jax.Array:
    """bool[G] — GMs whose recovery time fell in the round just ended."""
    return (fs.gm_up <= t) & (fs.gm_up > t - dt)


def gm_adoption(down: jax.Array, rnd: jax.Array):
    """Round-robin adoption map for down GMs.

    Returns ``(adopt int32[G], row_active bool[G], n_live int32[])``:
    ``adopt[g]`` is ``g`` for live GMs and, for down GMs, the live GM
    (rotating with the round index) that matches g's queue this round
    against its own view; ``row_active`` is false only when no GM is live
    (everything freezes); ``n_live`` is the live-GM count (heartbeat
    message accounting).  With no down GMs, ``adopt`` is the identity
    permutation.
    """
    G = down.shape[0]
    alive = ~down
    g_idx = jnp.arange(G, dtype=jnp.int32)
    n_live = jnp.sum(alive, dtype=jnp.int32)
    rank = jnp.cumsum(alive, dtype=jnp.int32) - 1        # live rank where alive
    live_of = (
        jnp.zeros(G, jnp.int32)
        .at[jnp.where(alive, rank, G)]
        .set(g_idx, mode="drop")                         # live rank -> GM id
    )
    adopt = jnp.where(
        alive, g_idx, live_of[(g_idx + rnd) % jnp.maximum(n_live, 1)]
    )
    return adopt, alive | (n_live > 0), n_live


# ---------------------------------------------------------------------------
# backend-neutral fault plans
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WorkerFailure:
    """One worker crash.  ``recover=None`` means instant restart (the event
    backend's only mode: the LM restarts the worker, the task re-runs)."""

    worker: int
    time: float
    recover: Optional[float] = None


@dataclass(frozen=True)
class GmOutage:
    """One megha GM down-window ``[time, recover)`` (§3.5)."""

    gm: int
    time: float
    recover: float


@dataclass(frozen=True)
class FaultPlan:
    """Backend-neutral fault description for ``run_simulation(faults=...)``.

    Compiles to a dense ``FaultSchedule`` for simx (``to_schedule``) or
    installs imperative hooks on the event loop (``install_events``).
    """

    worker_failures: tuple[WorkerFailure, ...] = ()
    gm_outages: tuple[GmOutage, ...] = ()
    heartbeat_delay: float = 0.0  # seconds added to megha's heartbeat period

    def _validate(self) -> None:
        """Shared plan validation (both backends fail fast identically):
        one failure per worker and one outage per GM — the dense schedule
        holds a single window per entity, so duplicates would silently
        drop all but the last entry and diverge from the event backend —
        and recovery may not precede the failure."""
        workers = [wf.worker for wf in self.worker_failures]
        if len(set(workers)) != len(workers):
            raise ValueError(
                "duplicate worker in FaultPlan: the dense schedule holds "
                "one crash window per worker"
            )
        gms = [go.gm for go in self.gm_outages]
        if len(set(gms)) != len(gms):
            raise ValueError(
                "duplicate GM in FaultPlan: the dense schedule holds one "
                "down window per GM"
            )
        for wf in self.worker_failures:
            if wf.recover is not None and wf.recover < wf.time:
                raise ValueError(f"worker {wf.worker}: recover before crash")
        for go in self.gm_outages:
            if go.recover < go.time:
                raise ValueError(f"gm {go.gm}: recover before failure")

    def to_schedule(
        self, num_workers: int, num_gms: int, dt: float
    ) -> FaultSchedule:
        self._validate()
        down = np.full(num_workers, np.inf, np.float32)
        up = np.full(num_workers, np.inf, np.float32)
        for wf in self.worker_failures:
            if not (0 <= wf.worker < num_workers):
                raise ValueError(f"worker {wf.worker} outside [0, {num_workers})")
            down[wf.worker] = wf.time
            up[wf.worker] = wf.time if wf.recover is None else wf.recover
        gdown = np.full(num_gms, np.inf, np.float32)
        gup = np.full(num_gms, np.inf, np.float32)
        for go in self.gm_outages:
            if not (0 <= go.gm < num_gms):
                raise ValueError(f"gm {go.gm} outside [0, {num_gms})")
            gdown[go.gm] = go.time
            gup[go.gm] = go.recover
        return FaultSchedule(
            worker_down=jnp.asarray(down),
            worker_up=jnp.asarray(up),
            gm_down=jnp.asarray(gdown),
            gm_up=jnp.asarray(gup),
            hb_extra_rounds=jnp.int32(max(0, round(self.heartbeat_delay / dt))),
        )

    def install_events(self, sched, loop) -> None:
        """Install this plan as event-backend fault hooks.

        Only megha implements the paper's fault hooks; worker down-windows
        and heartbeat perturbation have no event-backend counterpart and
        must run on simx.
        """
        self._validate()
        cfg = getattr(sched, "cfg", None)
        if cfg is not None:
            for wf in self.worker_failures:
                nw = getattr(cfg, "num_workers", None)
                if nw is not None and not (0 <= wf.worker < nw):
                    raise ValueError(f"worker {wf.worker} outside [0, {nw})")
            for go in self.gm_outages:
                ng = getattr(cfg, "num_gms", None)
                if ng is not None and not (0 <= go.gm < ng):
                    raise ValueError(f"gm {go.gm} outside [0, {ng})")
        if self.heartbeat_delay:
            raise ValueError(
                "heartbeat_delay perturbation requires backend='simx' "
                "(the event backend's interval is a config constant)"
            )
        if self.worker_failures and not hasattr(sched, "fail_worker"):
            raise ValueError(
                f"scheduler {sched.name!r} has no fault hooks; fault "
                "injection on the events backend requires megha "
                "(use backend='simx' for the baselines)"
            )
        if self.gm_outages and not hasattr(sched, "fail_gm"):
            raise ValueError(
                f"scheduler {sched.name!r} has no GMs; gm_outages apply "
                "to megha only"
            )
        for wf in self.worker_failures:
            if wf.recover is not None and wf.recover > wf.time:
                raise ValueError(
                    "worker down-windows require backend='simx' (the event "
                    "backend restarts crashed workers instantly)"
                )
            loop.push_at(wf.time, lambda w=wf.worker: sched.fail_worker(w))
        for go in self.gm_outages:

            def _fail(go=go):
                orphaned = sched.fail_gm(go.gm)
                loop.push_at(go.recover, lambda g=go.gm: sched.recover_gm(g))
                # §3.5 availability contract: orphaned jobs resubmit and are
                # rerouted round-robin to the live GMs.
                for job in orphaned:
                    sched.submit(job)

            loop.push_at(go.time, _fail)


def fault_grid_schedule(
    num_workers: int,
    num_gms: int,
    fractions: Sequence[float],
    *,
    fail_time: float,
    outage: float,
    gm_outages: int = 0,
    dt: float = 0.05,
    heartbeat_delay: float = 0.0,
    seed: int = 0,
) -> FaultSchedule:
    """A severity grid as ONE batched schedule (leading axis = fraction).

    Point ``i`` crashes ``round(fractions[i] * num_workers)`` workers (a
    fixed seeded permutation, so higher severities kill supersets) at
    ``fail_time``, down for ``outage`` seconds.  Every nonzero-severity
    point additionally takes ``gm_outages`` GMs (megha only; capped to
    keep one live) down over the same window.  Feed the result to
    ``vmap(simulate_fixed)`` — ``sweep.fig4_sweep`` wraps this into the
    compiled Fig. 4 program.
    """
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_workers)
    gperm = rng.permutation(num_gms)
    F = len(fractions)
    down = np.full((F, num_workers), np.inf, np.float32)
    up = np.full((F, num_workers), np.inf, np.float32)
    gdown = np.full((F, num_gms), np.inf, np.float32)
    gup = np.full((F, num_gms), np.inf, np.float32)
    for i, f in enumerate(fractions):
        if not (0.0 <= f < 1.0):
            raise ValueError("fault fractions must lie in [0, 1)")
        k = int(round(f * num_workers))
        down[i, perm[:k]] = fail_time
        up[i, perm[:k]] = fail_time + outage
        if f > 0.0 and gm_outages:
            g = min(gm_outages, num_gms - 1)  # always keep one GM live
            gdown[i, gperm[:g]] = fail_time
            gup[i, gperm[:g]] = fail_time + outage
    return FaultSchedule(
        worker_down=jnp.asarray(down),
        worker_up=jnp.asarray(up),
        gm_down=jnp.asarray(gdown),
        gm_up=jnp.asarray(gup),
        hb_extra_rounds=jnp.full(
            F, max(0, round(heartbeat_delay / dt)), jnp.int32
        ),
    )
