"""Shared round-stage runtime for the simx scheduler matrix.

Every simx scheduler advances the datacenter through the SAME round
pipeline; only the dispatch logic in the middle differs.  This module owns
that pipeline, the helpers each stage is built from, and the rule registry
the drivers (``engine``, ``sweep``, ``benchmarks``) iterate over — so a
new scheduler is one ``Rule`` (init + dispatch builder), not a fifth
re-implementation of the round machinery (the omniscient oracle in
``repro.simx.oracle`` is the existence proof: ~130 lines).

**The stage contract** (``compose_step``), in execution order:

  1. **faults** — ``fault_stage``: crashed workers lose their in-flight
     task (re-pended) and read busy until recovery.  Compiled out entirely
     when ``faults is None``; an empty schedule is a bitwise no-op.
  2. **complete** — ``completion_masks``: ground-truth free/completed-now
     masks from ``worker_finish`` crossing the round time.  Completion
     detection is implicit (``task_finish``/``worker_finish`` are recorded
     at launch), so this stage is two elementwise compares, no scatter.
  3. **rule.dispatch** — the scheduler-specific stage: match/bind/launch
     decisions, built from the shared windowed-FIFO (``slice_rows``,
     ``sorted_fifo``, ``window_launched``, ``launched_lead``) and launch
     bookkeeping (``apply_launch``) helpers.  Receives the post-fault
     arrays, the stage-2 masks, and the crash-loss mask (for FIFO head
     rollback); returns the state-field updates as a dict — under
     telemetry, optionally including a ``"telemetry"`` dict of per-round
     counters (launches + rule extras).
  4. **telemetry** (optional, ``compose_step(..., telemetry=True)``) —
     the runtime pops the rule's counter dict, adds the per-round deltas
     of the shared state counters, and the step returns
     ``(state, counters)`` for the decimated in-scan collection driver
     (``repro.simx.telemetry``).  Disabled (the default), nothing is
     built and the program is exactly the telemetry-free one (pinned
     bitwise by ``tests/test_simx_telemetry.py``).
  5. **provenance** (optional, ``compose_step(..., provenance=True)``) —
     the step's carry becomes ``(state, Provenance)`` and the runtime
     derives each round's per-task lifecycle transitions (eligible /
     attempt / launch / finish rounds, fault re-pends, placement
     identity) from the state delta, folding in the rule's optional
     ``"provenance"`` extras dict (``attempt`` / ``stale`` /
     ``authority`` — see ``repro.simx.provenance``).  Disabled (the
     default), nothing is built — same bitwise guarantee as telemetry
     (pinned by ``tests/test_simx_provenance.py``).
  6. **metrics/advance** — the runtime folds the updates into the carried
     state, accumulates the ``lost`` counter, and advances ``t``/``rnd``.

Drivers stay carry-shape agnostic via ``carry_state`` (the state leaf of
a possibly-tuple carry) — ``scan_rounds`` itself is pytree-generic.

Reporting shares one in-jit reduction too: ``job_delays_from_state`` is
the single Eq. 2 job-delay computation behind both ``sweep.point_summary``
(reduced inside the compiled grid) and ``engine.SimxRun`` (materialized to
numpy) — pinned equal by ``tests/test_simx_runtime.py``.

How to add a rule: see ``docs/simx_runtime.md``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.match import match_ranks_batched
from repro.simx.faults import FaultSchedule, apply_worker_faults
from repro.simx.state import QueueState, SimxConfig, TaskArrays

#: rank-and-select primitive: (avail bool[B, N], n int32[B]) -> ranks
#: int32[B, N] (rank of each selected column, -1 where unselected).
MatchFn = Callable[[jax.Array, jax.Array], jax.Array]


def default_match_fn(use_pallas: bool = False, block_rows: int = 64) -> MatchFn:
    """The match primitive every rule ranks-and-selects with: the jnp
    reference on every platform by default.  ``use_pallas=True`` selects
    the batched Pallas kernel instead; it stays opt-in until a chip
    benchmark has measured it against the jnp scan.  The kernel is
    compiled on a TPU and interpreted on the CPU
    (``repro.kernels.match.default_interpret``) — interpret mode is orders
    of magnitude slower than XLA inside a scanned hot loop.

    ``block_rows`` sizes the kernel's VMEM tile; the kernel pads each row
    to ``block_rows * 128`` lanes, so wide-and-few matches (megha's
    [G, W] GM rows, the oracle's [1, W] global row) want the default while
    narrow-and-many ones (the sparrow/eagle [W, R] head-of-queue pick,
    R ≲ 64) should pass ``block_rows=1``."""
    if use_pallas:
        return partial(match_ranks_batched, block_rows=block_rows)
    return ref.match_ranks_batched_ref


# ---------------------------------------------------------------------------
# stage helpers: windowed FIFOs, launch bookkeeping, completion masks
# ---------------------------------------------------------------------------


def slice_rows(mat: jax.Array, starts: jax.Array, width: int) -> jax.Array:
    """Per-row dynamic windows: row i of the result is
    ``mat[i, starts[i] : starts[i] + width]`` (rows must be pre-padded so
    the slice never leaves the array)."""
    return jax.vmap(
        lambda row, s: jax.lax.dynamic_slice(row, (s,), (width,))
    )(mat, starts)


def sorted_fifo(queued: jax.Array, width: int) -> jax.Array:
    """Window positions of the queued entries in FIFO order (``width`` =
    none): sorting queued positions ahead of the ``width`` sentinels
    preserves task-index (== FIFO) order, so the r-th launch rank maps to
    ``sorted_fifo(...)[..., r]`` even when launched tasks punch holes
    mid-window."""
    pos = jnp.broadcast_to(
        jnp.arange(width, dtype=jnp.int32), queued.shape
    )
    return jnp.sort(jnp.where(queued, pos, width), axis=-1)


def finish_pad(task_finish: jax.Array) -> jax.Array:
    """``task_finish`` with a ``-inf`` pad slot so windowed gathers of the
    out-of-bounds sentinel task read as launched."""
    return jnp.concatenate([task_finish, jnp.float32([-jnp.inf])])


def window_launched(fpad: jax.Array, wtask: jax.Array, num_tasks: int) -> jax.Array:
    """bool — which window entries are already launched (pad sentinels
    count as launched, so head advance can run through them)."""
    return ~jnp.isinf(fpad[wtask]) | (wtask >= num_tasks)


def launched_lead(launched: jax.Array) -> jax.Array:
    """int32 — length of each window's launched prefix (the amount the
    FIFO head pointer advances this round)."""
    return jnp.sum(
        jnp.cumprod(launched.astype(jnp.int32), axis=-1), axis=-1
    )


def select_from_window(
    ranks: jax.Array, fifo_pos: jax.Array, wtask: jax.Array, num_tasks: int
) -> jax.Array:
    """Map match ranks to window task ids: rank r serves the r-th queued
    window position (``sorted_fifo``), which indexes the window's task
    ids; unmatched lanes (rank < 0) read the ``num_tasks`` sentinel.
    Works batched ([G, C] windows with [G, K] ranks) and flat ([C] with
    [W]).  Megha/pigeon keep phase-specific variants (a -1 sentinel
    feeding the proposal masks, high/low queue splits)."""
    width = fifo_pos.shape[-1]
    sel_pos = jnp.take_along_axis(
        fifo_pos, jnp.clip(ranks, 0, width - 1), axis=-1
    )
    sel = jnp.take_along_axis(
        wtask, jnp.clip(sel_pos, 0, width - 1), axis=-1
    )
    return jnp.where(ranks >= 0, sel, num_tasks)


def apply_launch(
    launch: jax.Array,
    task_pick: jax.Array,
    start: jax.Array,
    dur_pad: jax.Array,
    task_finish: jax.Array,
    worker_finish: jax.Array,
    worker_task: jax.Array,
    num_tasks: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Apply one phase's launches ([W]-space masks) to the task/worker
    state: the completion time is known at launch, so both ``task_finish``
    and ``worker_finish`` are recorded as ``start + duration`` here — one
    [W]-wide scatter — and completions stay implicit forever after."""
    lt = jnp.where(launch, task_pick, num_tasks)
    fin = start + dur_pad[jnp.minimum(task_pick, num_tasks)]
    task_finish = task_finish.at[lt].set(fin, mode="drop")
    worker_finish = jnp.where(launch, fin, worker_finish)
    worker_task = jnp.where(launch, task_pick, worker_task)
    return task_finish, worker_finish, worker_task


def completion_masks(
    worker_finish: jax.Array, t: jax.Array, dt: float
) -> tuple[jax.Array, jax.Array]:
    """(free bool[W], completed-now bool[W]) ground truth at round start:
    free iff the recorded finish time has passed, completed-now iff it
    fell inside the round window just ended."""
    free = worker_finish <= t
    return free, free & (worker_finish > t - dt)


def fault_stage(
    faults: Optional[FaultSchedule],
    t: jax.Array,
    dt: float,
    task_finish: jax.Array,
    worker_finish: jax.Array,
    worker_task: jax.Array,
    num_tasks: int,
):
    """Stage 1: the crash transition shared by every rule.  Returns
    ``(task_finish, worker_finish, lost_w, n_lost)``; with ``faults=None``
    the arrays pass through untouched and ``lost_w``/``n_lost`` are None
    (the stage compiles out — rules guard their rollback on it)."""
    if faults is None:
        return task_finish, worker_finish, None, None
    return apply_worker_faults(
        faults, t, dt, task_finish, worker_finish, worker_task, num_tasks
    )


# ---------------------------------------------------------------------------
# the round pipeline
# ---------------------------------------------------------------------------

#: Dispatch stage: (state, t, task_finish0, worker_finish0, free, comp,
#: lost_w) -> dict of state-field updates (everything except t/rnd/lost,
#: which the runtime advances).  Under ``telemetry=True`` the dict MAY
#: additionally carry a ``"telemetry"`` key: a dict of per-round int32
#: scalar counters (``launches`` expected of every rule, plus
#: rule-specific extras) that the runtime pops before folding updates.
DispatchFn = Callable[..., dict]

#: The shared counters whose per-round deltas the telemetry stage derives
#: itself (dispatch never has to report them): new - old of the carried
#: ``CoreState`` accumulators, plus the ``QueueState`` health counters
#: for reservation-queue rules.
TELEMETRY_CORE_COUNTERS = ("messages", "probes", "inconsistencies", "lost")
TELEMETRY_QUEUE_COUNTERS = ("res_overflow", "probe_lag")

#: ``CoreState`` fields the RUNTIME advances inside ``compose_step`` —
#: the time/round clock and the crash-loss accumulator.  A dispatch
#: stage's update dict must never contain them (the runtime would fold
#: the rule's write and then overwrite/double-advance it); the simxlint
#: SC101 rule enforces this statically over every rule module.
RUNTIME_OWNED_FIELDS = ("t", "rnd", "lost")

#: The stage contract ``compose_step`` assembles, in execution order,
#: with each stage's owner and the state fields it may write.  This is
#: the machine-readable form of the module-docstring prose contract —
#: ``repro.analysis.simxlint`` derives its dispatch-write rule from it
#: and ``docs/simx_runtime.md`` renders it.
STAGE_TABLE = (
    # (stage,        owner,      writes)
    ("faults",    "runtime", ("task_finish", "worker_finish", "lost")),
    ("complete",  "runtime", ()),            # pure masks, no writes
    ("dispatch",  "rule",    "any-but-runtime-owned"),
    ("telemetry", "runtime", ()),            # derives deltas, no writes
    ("metrics",   "runtime", ("t", "rnd", "lost")),
)

#: Round-index budget: ``rnd`` (and every lifecycle round in
#: ``Provenance``) is int32, so a run may advance at most this many
#: rounds before the counter would wrap.  Kept well under 2**31 -- 1 so
#: round arithmetic (``rnd + heartbeat_rounds``, round -> seconds
#: multiplies) cannot overflow either; ``engine``/``stream`` refuse
#: budgets past it with a clear error instead of wrapping silently.
MAX_ROUND_BUDGET = 2**31 - 2**20


def check_round_budget(num_rounds: int, where: str = "scan_rounds") -> None:
    """Fail fast when a static round budget would overflow the int32
    round clock (a ~100-day steady-state span at dt=0.05 — reachable by a
    mistyped ``horizon``/``max_rounds``, so refuse loudly)."""
    if num_rounds > MAX_ROUND_BUDGET:
        raise OverflowError(
            f"{where}: {num_rounds} rounds exceeds the int32 round-clock "
            f"budget ({MAX_ROUND_BUDGET}); the rnd counter and the "
            "provenance lifecycle rounds would wrap silently. Split the "
            "run or raise dt."
        )


def carry_state(carry):
    """The scheduler state leaf of a scan carry: under provenance the
    carry is ``(state, Provenance)``, otherwise the state itself.  Purely
    host-level (the carry's python structure is static), so using it in a
    driver changes nothing about the compiled program."""
    return carry[0] if isinstance(carry, tuple) else carry


def compose_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    dispatch: DispatchFn,
    faults: Optional[FaultSchedule] = None,
    telemetry: bool = False,
    provenance: bool = False,
) -> Callable:
    """Assemble one rule's jittable round step from the stage contract:
    ``faults -> complete -> dispatch -> telemetry -> metrics/advance``
    (module docstring).  ``dispatch`` owns everything scheduler-specific;
    the runtime owns the fault transition, the ground-truth masks, the
    ``lost`` accumulator, and the time/round advance.

    With ``telemetry=True`` the step returns ``(state, counters)`` —
    ``counters`` merges the rule's per-round ``"telemetry"`` dict with the
    runtime-derived deltas of the shared state counters — for the
    decimated collection driver (``repro.simx.telemetry``).  With
    ``telemetry=False`` (the default) the step returns the state alone and
    the stage compiles out entirely: nothing telemetry-related is ever
    built, so the program is exactly the pre-telemetry one (final states
    pinned bitwise by ``tests/test_simx_telemetry.py``).

    With ``provenance=True`` the carry becomes ``(state, Provenance)``:
    the runtime pops the rule's optional ``"provenance"`` extras and
    advances the per-task lifecycle arrays after folding the state
    updates (``repro.simx.provenance.advance_provenance``).  Disabled,
    nothing provenance-related is built — the same bitwise compile-out
    guarantee as the telemetry flag."""
    from repro.simx.provenance import advance_provenance

    T = tasks.num_tasks

    def step(carry):
        s = carry[0] if provenance else carry
        t = s.t
        task_finish0, worker_finish0, lost_w, n_lost = fault_stage(
            faults, t, cfg.dt, s.task_finish, s.worker_finish, s.worker_task, T
        )
        free, comp = completion_masks(worker_finish0, t, cfg.dt)
        updates = dispatch(s, t, task_finish0, worker_finish0, free, comp, lost_w)
        tel = updates.pop("telemetry", None)
        pv = updates.pop("provenance", None)
        if n_lost is not None:
            updates["lost"] = s.lost + n_lost
        new = s.replace(t=t + cfg.dt, rnd=s.rnd + 1, **updates)
        if provenance:
            out = (
                new,
                advance_provenance(carry[1], s, new, task_finish0, tasks, pv or {}),
            )
        else:
            out = new
        if not telemetry:
            return out
        counters = dict(tel or {})
        for f in TELEMETRY_CORE_COUNTERS:
            counters[f] = getattr(new, f) - getattr(s, f)
        if isinstance(new, QueueState):
            for f in TELEMETRY_QUEUE_COUNTERS:
                counters[f] = getattr(new, f) - getattr(s, f)
        return out, counters

    return step


def scan_rounds(step: Callable, state, num_rounds: int):
    """Advance ``state`` by ``num_rounds`` rounds under one lax.scan.

    ``num_rounds`` is static (a python int even under trace), so the
    int32 round-clock overflow check is free here; the carried ``rnd``
    itself may be a tracer and is checked by the host-side drivers
    (``engine.run_to_completion``, ``stream.run_steady_state``)."""
    check_round_budget(num_rounds)
    state, _ = jax.lax.scan(
        lambda s, _: (step(s), None), state, None, length=num_rounds
    )
    return state


# ---------------------------------------------------------------------------
# the rule registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Rule:
    """One scheduler in the simx matrix.

    ``build_step(cfg, tasks, key, *, match_fn, pick_fn, faults,
    telemetry)`` returns the jittable round step (normally a
    ``compose_step`` of the rule's dispatch stage — with
    ``telemetry=True`` the step reports per-round counters, see
    ``compose_step``); ``init(cfg, tasks)`` the fresh scan carry.
    ``match_fn`` is the wide rank-and-select (GM rows / central FIFOs /
    group picks), ``pick_fn`` the narrow [W, R] head-of-queue pick of the
    reservation-queue rules — a rule consumes what it needs and ignores
    the rest.  ``needs_grid`` marks rules whose worker count must divide
    into the GM x LM partition grid (the drivers shave it via
    ``grid_workers`` before building the config)."""

    name: str
    init: Callable[[SimxConfig, TaskArrays], Any]
    build_step: Callable[..., Callable]
    needs_grid: bool = False
    has_queues: bool = False  # carries [W, R] reservation-queue probe state


#: name -> Rule, in registration order (the canonical scheduler order:
#: the four paper schedulers, then the oracle baseline).
RULES: dict[str, Rule] = {}


def register_rule(rule: Rule) -> Rule:
    """Register a scheduler rule; every driver (``engine``, ``sweep``,
    benchmarks) picks it up with no further wiring."""
    if rule.name in RULES:
        raise ValueError(f"rule {rule.name!r} already registered")
    RULES[rule.name] = rule
    return rule


def get_rule(name: str) -> Rule:
    try:
        return RULES[name.lower()]
    except KeyError:
        raise ValueError(
            f"simx backend implements {tuple(RULES)}, not {name!r}"
        ) from None


def simulate_fixed(
    name: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    seed: jax.Array | int,
    num_rounds: int,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry=None,
    provenance: bool = False,
):
    """Run any registered rule exactly ``num_rounds`` rounds from a fresh
    DC — a pure function of ``seed`` (and the ``faults`` leaves), so an
    entire sweep grid runs as ``jax.vmap(simulate_fixed, ...)`` in one
    compiled program.  This replaces the per-module ``simulate_fixed``
    quadruplet (those survive as thin wrappers) and the hand-maintained
    ``SIMULATE_FIXED`` dict in ``sweep``.

    ``telemetry`` (a ``repro.simx.telemetry.TelemetryConfig``) switches on
    the in-scan telemetry stage: the return value becomes
    ``(state, Timeline)`` — the decimated per-round series plus the
    in-jit delay histogram, still fully traceable/vmappable.  ``None``
    (the default) builds exactly the telemetry-free program.

    ``provenance=True`` switches on the lifecycle stage: the returned
    state becomes the ``(state, Provenance)`` carry (the Timeline, when
    also enabled, stays the second element of the outer tuple)."""
    rule = get_rule(name)
    key = jax.random.PRNGKey(seed) if jnp.ndim(seed) == 0 else seed
    step = rule.build_step(
        cfg, tasks, key, match_fn=match_fn, pick_fn=pick_fn, faults=faults,
        telemetry=telemetry is not None, provenance=provenance,
    )
    state = rule.init(cfg, tasks)
    if provenance:
        from repro.simx.provenance import init_provenance

        state = (state, init_provenance(tasks.num_tasks))
    if telemetry is None:
        return scan_rounds(step, state, num_rounds)
    from repro.simx import telemetry as tlm  # runtime <- telemetry cycle guard

    return tlm.scan_rounds_telemetry(
        step, state, num_rounds, telemetry, cfg, tasks, faults
    )


# ---------------------------------------------------------------------------
# the shared job-delay reduction (Eq. 2)
# ---------------------------------------------------------------------------


def job_delays_from_state(
    task_finish: jax.Array, t: jax.Array, tasks: TaskArrays
) -> tuple[jax.Array, jax.Array]:
    """The ONE in-jit job-delay reduction every reporter routes through.

    A task is done iff its recorded finish time has passed ``t``; a job
    finishes at its last task's finish.  Returns ``(delays float32[J],
    job_finish float32[J])`` with ``delays = finish - submit - ideal``
    (Eq. 2), nan for unfinished jobs (``job_finish`` reads ``+/-inf``
    there).  ``sweep.point_summary`` percentiles this inside the compiled
    grid; ``engine.SimxRun`` materializes it to numpy — both see
    identical values (pinned by ``tests/test_simx_runtime.py``)."""
    fin = jnp.where(task_finish <= t, task_finish, jnp.inf)
    job_finish = jnp.full(tasks.num_jobs, -jnp.inf).at[tasks.job].max(fin)
    delays = job_finish - tasks.job_submit - tasks.job_ideal
    delays = jnp.where(jnp.isfinite(job_finish), delays, jnp.nan)
    return delays, job_finish
