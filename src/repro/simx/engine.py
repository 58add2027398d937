"""simx engine: fixed-timestep, JAX-compiled datacenter simulation.

**Round-synchronous approximation.** The event-driven backend
(``repro.core``) fires every message, launch, and completion at its exact
simulated timestamp, one Python callback at a time.  simx instead advances
the whole datacenter in fixed rounds of ``cfg.dt`` simulated seconds under
``jax.lax.scan``: within a round, completions are processed first, then
(periodically) heartbeats, then every GM matches and every LM verifies —
simultaneously, with conflicts arbitrated by a per-round rotating GM
priority.  The semantic differences vs. the event backend:

  * **Time quantization** — scheduling reactions (a queued task seeing a
    freed worker, an arrival being matched) happen at the next round
    boundary instead of one network hop after the triggering event, adding
    up to ``dt`` of latency per reaction (launch/finish timestamps
    themselves stay exact: ``start = round_time + hops``,
    ``finish = start + duration``).  Pick ``dt`` well under the typical task
    duration and the aggregate delay distributions converge to the event
    backend's (the parity tests pin this).
  * **Message interleaving** — the event backend serializes same-time
    events in insertion order; simx resolves a whole round's claims at
    once, so per-task placements can differ even though aggregate behavior
    matches.  Runs are still bit-deterministic for a fixed (config, seed).
  * **Batch granularity** — per-(GM, LM) request batching is implicit (one
    round = one batch) rather than bounded by ``batch_limit``.

Per-scheduler contract addenda (megha/sparrow specifics live in their
module docstrings; these are the eagle/pigeon counterparts):

  * **Eagle probe-rejection timing** — SSS rejection and re-routing are
    resolved *within the arrival round*, against the ground-truth set of
    long-running workers at that instant.  The event backend spreads the
    reject -> resend chain over network hops and consults a possibly stale
    SS bit-vector adopted from the previous rejection; simx collapses the
    chain to (at most) two instantaneous re-routes — once to a random
    worker, once to the never-long short partition — so rejected probes
    reach their final node up to ``2 * hop`` earlier and with a slightly
    higher resend rate (random re-route targets stand in for SS-clear
    targeting).  The central long-job scheduler launches only onto
    actually-free long-partition workers: a long task whose event-backend
    counterpart would head-of-line block behind a running short task
    instead stays queued centrally, which shifts (not drops) its wait.
  * **Sparrow/eagle reservation queues** — probe/reservation state is a
    capped per-worker queue ``int32[W, R]`` (R = ``SimxConfig.queue_cap``),
    not a dense [J, W] mask, so carried state is independent of the trace
    length.  Three sub-approximations follow: (1) probes are inserted
    through a bounded per-round window over the arrival-ordered edge list
    (``SimxConfig.insert_window``) — an arrival burst wider than the
    window lands over the following rounds (the auto window drains a
    whole-trace burst in ~32 rounds; totals, and hence probe/message
    counters, are unchanged), and every saturated round increments the
    ``probe_lag`` counter so the added latency is observable — a nonzero
    value on a latency-sensitive study means raise ``probe_window``; (2)
    a probe aimed at a worker whose queue is
    already full is dropped and counted in ``res_overflow`` — the event
    backend's unbounded per-worker queues never drop, so a deliberately
    undersized R trades placement quality for memory while the *orphan
    rescue* below preserves liveness; (3) a job with pending work, all of
    whose probes were dropped (or — under faults — whose every reservation
    sits on a dead worker), is servable by any idle worker until a
    reservation becomes live again.  With the auto cap, overflow is zero
    on load-feasible traces and the encoding is behavior-equivalent to the
    retired dense mask (pinned bitwise against an in-test dense reference
    by ``tests/test_simx_queues.py``).
  * **Pigeon group-master quantization** — each group coordinator serves
    its high/low FIFOs once per round: a task arriving to a group with a
    free worker launches at the round boundary instead of on arrival
    (within the global ``dt`` quantization bound), and weighted fair
    queuing is applied as a per-round *allocation* of the group's free
    unreserved workers (``wfq_weight`` high : 1 low, phase carried by the
    ``since_low`` counter) rather than per-dequeue alternation.  Because
    every launch in a round shares one start time, only the high/low
    counts are observable — the closed form is exact whenever either queue
    drains within the round and a faithful ratio under sustained
    contention.

Fault-injection contract (``faults=``, see ``repro.simx.faults``): fault
schedules are dense per-worker / per-GM crash and recovery *times*, but
the round-synchronous engine only observes them at round boundaries, so

  * **Fault-timing quantization** — a crash or recovery taking effect at
    time ``x`` is applied at the first round boundary ``t >= x`` (up to
    ``dt`` late, like every other scheduling reaction).  An instant-restart
    failure (``up == down``) therefore returns the worker at the next
    boundary rather than immediately.
  * **Loss granularity** — the in-flight task lost to a crash is re-pended
    at the crash round and becomes schedulable the same round; the event
    backend re-queues it one hop after the LM notices.  Schedulers re-serve
    it through their normal path (megha/pigeon/eagle-long: FIFO-head
    rollback, so a few rounds may pass before a distant window position is
    re-examined; sparrow/eagle-short: the pending mask itself).
  * **Megha GM windows** — a down GM's queue (including arrivals, which
    round-synchronous execution makes indistinguishable from queued tasks)
    is matched each round by a live GM chosen round-robin per round,
    against the *adopter's* eventually-consistent view; the event backend
    instead resubmits orphaned jobs wholesale and reroutes new arrivals,
    so under GM faults events re-run already-completed tasks while simx
    continues partial jobs — aggregate delays track, per-job timings drift
    by up to the re-run cost.  Recovery resets the GM's view from LM truth
    in-round (``rebuild_from_heartbeats`` is a message exchange in events).
  * **Dead-worker visibility** — a down worker reads busy-until-recovery
    in ground truth; megha's stale views discover this through the normal
    inconsistency/piggyback/heartbeat machinery, sparrow/eagle reservations
    on it simply wait (orphaned jobs are rescued by any idle worker), and
    eagle's SSS bounces probes off it at the arrival round.

An *empty* schedule is bit-identical to the fault-free program (pinned by
``tests/test_simx_faults.py``).

Streaming-window addendum (``repro.simx.stream``): the drivers here run a
fully materialized trace; ``run_steady_state`` instead streams an
open-loop arrival process through a fixed-capacity ring-buffer window
(``layout=`` on each rule's step builder), refilled on the host between
jitted segments.  Two semantic deltas on top of the contract above:

  * **Capacity-bound admission** — a job enters the window when a slot
    frees, not at its submit time; it keeps its *original* submit time,
    so slot-wait accrues as queuing delay (overload is measured, not
    dropped), but probe/arrival messages are counted at admission.
  * **Refill-granularity retirement** — a completed job occupies its
    slots until the next ``rounds_per_refill`` boundary, so the window's
    effective capacity shrinks by up to one segment's completions.

Within a segment the round dynamics are the fixed path's, pinned by
``tests/test_simx_streaming.py`` (bitwise for megha/pigeon/oracle;
distribution-level for sparrow/eagle, whose probe targets are
host-sampled per global job id).  Recipe: docs/steady_state.md.

What this buys: the entire simulation is one compiled program — a Fig. 2
sweep point at 50k workers is a ``scan`` over dense ``[G, W]`` arrays, and a
whole (seed x load) grid runs as one ``vmap`` (``repro.simx.sweep``), with
fault-severity grids (Fig. 4) vmapping the same way over schedule leaves
(``repro.simx.sweep.fig4_sweep``).  See ``benchmarks/bench_simx.py`` for
the events-vs-simx throughput comparison and the ``--faults`` grid.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.base import LONG_JOB_THRESHOLD
from repro.core.megha import grid_workers
from repro.core.metrics import JobRecord, RunMetrics, TaskRecord, classify_long
from repro.simx import runtime
from repro.simx.faults import FaultPlan, FaultSchedule, is_empty
from repro.simx.provenance import Provenance, init_provenance

# importing the rule modules registers them; canonical (paper) order first,
# then the oracle baseline — the registry preserves registration order
from repro.simx import megha as simx_megha  # noqa: F401
from repro.simx import sparrow as simx_sparrow  # noqa: F401
from repro.simx import eagle as simx_eagle  # noqa: F401
from repro.simx import pigeon as simx_pigeon  # noqa: F401
from repro.simx import oracle as simx_oracle  # noqa: F401
from repro.simx.runtime import scan_rounds  # noqa: F401 — re-export
from repro.simx.state import (
    CoreState,
    SimxConfig,
    TaskArrays,
    export_workload,
)
from repro.simx.telemetry import TelemetryConfig, Timeline
from repro.workload.traces import Workload

def __getattr__(name: str):
    """``SCHEDULERS`` is a LIVE view of the rule registry (the full
    Fig. 2 matrix plus the omniscient-oracle lower bound, in registration
    order) — a rule registered after import still shows up, keeping the
    'registering is all the wiring' contract honest for every driver
    that iterates it."""
    if name == "SCHEDULERS":
        return tuple(runtime.RULES)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def make_chunk_runner(
    step: Callable, chunk: int = 256, donate: bool = False
) -> Callable:
    """Jit a ``chunk``-round advance of ``step``; reuse it across runs to
    amortize compilation (a fresh jit per call would recompile).

    Returns ``(state, all_done bool[])`` — the completion probe is reduced
    INSIDE the compiled chunk, so ``run_to_completion``'s host check reads
    one ready scalar instead of dispatching a second device program per
    chunk (``bench_simx.py`` reports the saved dispatch overhead as the
    ``simx_doneprobe`` row).

    ``donate=True`` donates the carried state to the compiled chunk
    (``donate_argnums``) so XLA updates it in place instead of holding the
    old and new state live across each call — halving the carried-state
    footprint of the chunk loop.  The caller's input buffer is consumed:
    only the returned state is valid after the call (the ``simx_donation``
    bench row reports the measured wall/peak-memory deltas).  Off by
    default because callers that re-read a prior state (the doneprobe
    bench keeps every chunk's state alive) would see garbage."""

    def run(c):
        c = scan_rounds(step, c, chunk)
        s = runtime.carry_state(c)
        return c, jnp.all(s.task_finish <= s.t)

    return jax.jit(run, donate_argnums=(0,) if donate else ())


@partial(jax.jit, static_argnums=(0, 2))
def _run_tail(step: Callable, state, n: int):
    """Jitted remainder runner for ``run_to_completion``'s final partial
    chunk: advance exactly ``n < chunk`` rounds with the done probe reduced
    in-jit, mirroring ``make_chunk_runner``.  Cached on (step identity, n),
    so repeated runs with the same step (sweep loops, the bench harness)
    pay one extra compile per distinct tail length instead of falling off
    the fast path every call (``tests/test_simx_streaming.py`` pins the
    jitted tail bitwise against the eager ``scan_rounds`` it replaced)."""
    state = scan_rounds(step, state, n)
    s = runtime.carry_state(state)
    return state, jnp.all(s.task_finish <= s.t)


def run_to_completion(
    step: Callable,
    state,
    *,
    chunk: int = 256,
    max_rounds: int = 1_000_000,
    runner: Optional[Callable] = None,
    donate: bool = False,
):
    """Drive ``step`` in jitted ``chunk``-round scans until every task is
    done (or ``max_rounds`` as a runaway guard).  Returns the final state.

    A precompiled ``runner`` (from ``make_chunk_runner``) may be supplied to
    amortize compilation across runs; it MUST advance exactly ``chunk``
    rounds per call — pass the same chunk to both.

    ``donate=True`` builds the internal runner with state donation (see
    ``make_chunk_runner``); the caller's ``state`` argument is consumed.
    Ignored when a prebuilt ``runner`` is supplied — donation is a
    property of the compiled runner itself.

    ``max_rounds`` is exact: a final partial chunk runs through the jitted
    remainder runner (``_run_tail``), so the state never advances past the
    budget (this is what makes an ``until`` horizon cap precise) and a
    near-boundary budget stays on the compiled fast path."""
    runtime.check_round_budget(max_rounds, "run_to_completion(max_rounds=...)")
    run_chunk = (
        runner if runner is not None else make_chunk_runner(step, chunk, donate)
    )
    rounds = 0
    while rounds < max_rounds:
        n = min(chunk, max_rounds - rounds)
        if n == chunk:
            state, done = run_chunk(state)
        else:
            state, done = _run_tail(step, state, n)
        rounds += n
        if bool(done):
            break
    return state


def run_to_completion_telemetry(
    step: Callable,
    state,
    tel: TelemetryConfig,
    cfg: SimxConfig,
    tasks: TaskArrays,
    *,
    faults: FaultSchedule | None = None,
    chunk: int = 256,
    max_rounds: int = 1_000_000,
) -> tuple:
    """Telemetry counterpart of ``run_to_completion``: drive a
    telemetry-enabled ``step`` (returns ``(state, counters)`` per round) in
    jitted chunks of whole telemetry windows, collecting the decimated
    series blocks on the host.  Returns ``(state, Timeline)``.

    The chunk is rounded down to a multiple of ``tel.stride`` (min one
    window) so every chunk emits whole windows; a final partial chunk keeps
    ``max_rounds`` exact — its trailing ``< stride`` rounds advance the
    state but are not sampled, same as ``scan_rounds_telemetry``."""
    from repro.simx import telemetry as tlm

    runtime.check_round_budget(
        max_rounds, "run_to_completion_telemetry(max_rounds=...)"
    )

    stride = tel.stride
    chunk = max(stride, (chunk // stride) * stride)
    sample_fn = tlm.default_sample_fn(cfg, tasks, faults)

    @jax.jit
    def run_chunk(c):
        c, series = tlm.scan_blocks(step, c, chunk // stride, stride, sample_fn)
        s = runtime.carry_state(c)
        return c, series, jnp.all(s.task_finish <= s.t)

    blocks: list[dict] = []
    rounds = 0
    while rounds < max_rounds:
        n = min(chunk, max_rounds - rounds)
        if n == chunk:
            state, series, done = run_chunk(state)
            blocks.append(series)
        else:
            k = n // stride
            if k:
                state, series = tlm.scan_blocks(step, state, k, stride, sample_fn)
                blocks.append(series)
            if n - k * stride:
                state = tlm.advance_plain(step, state, n - k * stride)
            s = runtime.carry_state(state)
            done = jnp.all(s.task_finish <= s.t)
        rounds += n
        if bool(done):
            break
    if blocks:
        series = {
            key: np.concatenate([np.asarray(b[key]) for b in blocks])
            for key in blocks[0]
        }
    else:
        series = {}
    t_axis = series.pop("t", np.zeros(0, np.float32))
    s = runtime.carry_state(state)
    hist = tlm.delay_histogram(s.task_finish, s.t, tasks, tel)
    timeline = Timeline(
        t=jnp.asarray(t_axis),
        series={k: jnp.asarray(v) for k, v in series.items()},
        delay_hist=hist,
        stride=stride,
        dt=cfg.dt,
        delay_max=tel.delay_max,
    )
    return state, timeline


def estimate_rounds(cfg: SimxConfig, tasks: TaskArrays, slack: float = 4.0) -> int:
    """Upper-bound round count: arrival span + ``slack`` x the perfectly
    packed drain time + the longest task + one heartbeat interval."""
    span = (
        float(jnp.max(tasks.submit))
        + slack * float(jnp.sum(tasks.duration)) / cfg.num_workers
        + float(jnp.max(tasks.duration))
        + cfg.heartbeat_interval
        + 1.0
    )
    return int(math.ceil(span / cfg.dt))


@dataclass
class SimxRun:
    """A finished simx simulation plus everything needed to report it."""

    scheduler: str
    workload_name: str
    cfg: SimxConfig
    tasks: TaskArrays
    state: CoreState
    timeline: Optional[Timeline] = None
    provenance: Optional[Provenance] = None

    @property
    def end_time(self) -> float:
        return float(self.state.t)

    @property
    def tasks_completed(self) -> int:
        return int(jnp.sum(self.state.task_finish <= self.state.t))

    @property
    def lost_tasks(self) -> int:
        """In-flight tasks lost to worker crashes (each re-ran elsewhere)."""
        return int(self.state.lost)

    def job_finish_times(self) -> np.ndarray:
        """float64[J] job finish (max task finish; nan if any task
        unfinished — a launched-but-unfinished task carries a future
        finish time, which reads as not completed).  Routed through the
        runtime's shared in-jit reduction, so this is the SAME computation
        ``sweep.point_summary`` percentiles inside a compiled grid."""
        _, job_finish = runtime.job_delays_from_state(
            self.state.task_finish, self.state.t, self.tasks
        )
        out = np.asarray(job_finish, np.float64)
        return np.where(np.isfinite(out), out, np.nan)

    def job_delays(self) -> np.ndarray:
        """float64[J] JCT delay (Eq. 2) for completed jobs, nan otherwise
        (``runtime.job_delays_from_state``, materialized)."""
        delays, _ = runtime.job_delays_from_state(
            self.state.task_finish, self.state.t, self.tasks
        )
        return np.asarray(delays, np.float64)

    def delay_decomposition(self) -> dict[str, np.ndarray]:
        """Per-job delay split into the four provenance components (each
        float64[J], nan for unfinished jobs), summing exactly to
        ``job_delays()``.  Requires ``simulate_workload(provenance=True)``."""
        if self.provenance is None:
            raise ValueError(
                "run was built without provenance "
                "(simulate_workload(..., provenance=True))"
            )
        from repro.simx.provenance import decompose_delays

        d = decompose_delays(
            self.provenance, self.state.task_finish, self.state.t,
            self.tasks, self.cfg.dt,
        )
        return {k: np.asarray(v, np.float64) for k, v in d.items()}

    def span_events(self, pid: int = 1) -> list[dict]:
        """Chrome trace ``ph: "X"`` duration spans for this run's tasks on
        per-GM and per-worker tracks (``telemetry.provenance_spans``).
        Requires ``simulate_workload(provenance=True)``."""
        if self.provenance is None:
            raise ValueError(
                "run was built without provenance "
                "(simulate_workload(..., provenance=True))"
            )
        from repro.simx.telemetry import provenance_spans

        return provenance_spans(
            self.provenance, self.state, self.tasks, self.cfg,
            pid=pid, name=self.scheduler,
        )

    def to_run_metrics(self, include_tasks: bool = True) -> RunMetrics:
        """Materialize ``RunMetrics`` records so every event-backend consumer
        (``summary()``, plotting, percentile helpers) works unchanged.

        Record construction is a Python loop (one object per job/task) —
        fine for parity-scale traces, but sweep-scale callers (500k+ tasks)
        should pass ``include_tasks=False`` or read the dense arrays
        directly (``job_delays()``, ``state.task_finish``)."""
        m = RunMetrics(scheduler=self.scheduler, workload=self.workload_name)
        m.inconsistencies = int(self.state.inconsistencies)
        m.repartitions = int(self.state.repartitions)
        m.messages = int(self.state.messages)
        m.probes = int(self.state.probes)
        job_finish = self.job_finish_times()
        submit = np.asarray(self.tasks.job_submit, np.float64)
        ideal = np.asarray(self.tasks.job_ideal, np.float64)
        ntasks = np.asarray(self.tasks.job_ntasks)
        for j in range(self.tasks.num_jobs):
            m.jobs.append(
                JobRecord(
                    job_id=j,
                    submit_time=float(submit[j]),
                    ideal_jct=float(ideal[j]),
                    num_tasks=int(ntasks[j]),
                    finish_time=float(job_finish[j]),
                    is_long=classify_long(float(ideal[j]), LONG_JOB_THRESHOLD),
                )
            )
        if include_tasks:
            t_job = np.asarray(self.tasks.job)
            # late-binding paths queue at the worker; centrally scheduled
            # paths queue at the scheduling entity.  Eagle splits per task:
            # short jobs ride the probe path, long jobs the central FIFO
            # (matching the event backend's d_queue_* bookkeeping).
            if self.scheduler == "sparrow":
                worker_queue = np.ones(self.tasks.num_tasks, bool)
            elif self.scheduler == "eagle":
                worker_queue = (
                    np.asarray(self.tasks.job_est)[t_job]
                    < self.cfg.long_threshold
                )
            else:
                worker_queue = np.zeros(self.tasks.num_tasks, bool)
            t_dur = np.asarray(self.tasks.duration, np.float64)
            t_sub = np.asarray(self.tasks.submit, np.float64)
            t_fin_raw = np.asarray(self.state.task_finish, np.float64)
            # finish was recorded at launch as start + duration
            t_start = t_fin_raw - t_dur
            t_fin = np.where(t_fin_raw <= self.end_time, t_fin_raw, np.inf)
            hops = 3 * self.cfg.hop
            for i in range(self.tasks.num_tasks):
                tr = TaskRecord(
                    job_id=int(t_job[i]),
                    task_index=i,
                    duration=float(t_dur[i]),
                    submit_time=float(t_sub[i]),
                    start_time=float(t_start[i]) if np.isfinite(t_start[i]) else math.nan,
                    finish_time=float(t_fin[i]) if np.isfinite(t_fin[i]) else math.nan,
                )
                if np.isfinite(t_start[i]):
                    pre = max(0.0, t_start[i] - t_sub[i])
                    tr.d_comm = min(pre, hops)
                    wait = pre - tr.d_comm
                    if worker_queue[i]:
                        tr.d_queue_worker = wait
                    else:
                        tr.d_queue_scheduler = wait
                m.tasks.append(tr)
        return m


def simulate_workload(
    scheduler: str,
    workload: Workload,
    num_workers: int,
    *,
    num_gms: int = 8,
    num_lms: int = 8,
    heartbeat_interval: float = 5.0,
    probe_ratio: int = 2,
    long_threshold: float = LONG_JOB_THRESHOLD,
    short_partition_fraction: float = 0.10,
    num_distributors: int = 5,
    group_size: int = 40,
    reserved_per_group: int = 2,
    weight: int = 4,
    reserve_cap: int = 0,
    probe_window: int = 0,
    dt: float = 0.05,
    seed: int = 0,
    chunk: int = 256,
    max_rounds: Optional[int] = None,
    until: Optional[float] = None,
    use_pallas: bool = False,
    faults: FaultSchedule | FaultPlan | None = None,
    telemetry: TelemetryConfig | bool | None = None,
    provenance: bool = False,
) -> SimxRun:
    """Run one (scheduler, workload) simx simulation to completion.

    ``scheduler`` is any registered rule — the four paper schedulers or
    the ``"oracle"`` global-knowledge lower bound (``runtime.RULES``).
    Mirrors ``sim.simulator.run_simulation`` semantics; ``until`` caps the
    simulated time span instead of running until all tasks finish.
    Scheduler-specific knobs carry the event backend's names and defaults
    (``weight`` maps to ``SimxConfig.wfq_weight``; ``reserve_cap`` /
    ``probe_window`` size the sparrow/eagle reservation queues, 0 = auto).
    ``faults`` injects a
    fault schedule (a dense ``FaultSchedule`` or a backend-neutral
    ``FaultPlan``) into the compiled round step — see the module docstring
    for the fault-timing contract.

    ``telemetry`` (a ``TelemetryConfig``, or ``True`` for the defaults)
    collects the decimated in-scan series and delay histogram; the run's
    ``Timeline`` lands on ``SimxRun.timeline``.  ``None`` (the default)
    builds today's telemetry-free program bit-for-bit.

    ``provenance=True`` additionally carries the per-task lifecycle arrays
    (``repro.simx.provenance``) through the scan; the final ``Provenance``
    lands on ``SimxRun.provenance`` and feeds ``delay_decomposition()`` /
    ``span_events()``.  Disabled, the program is bit-identical to today's —
    the same guarantee as the telemetry flag.
    """
    name = scheduler.lower()
    rule = runtime.get_rule(name)
    tasks = export_workload(workload)
    if rule.needs_grid:
        num_workers = grid_workers(num_workers, num_gms, num_lms)
    cfg = SimxConfig(
        num_workers=num_workers,
        num_gms=num_gms,
        num_lms=num_lms,
        heartbeat_interval=heartbeat_interval,
        probe_ratio=probe_ratio,
        long_threshold=long_threshold,
        short_partition_fraction=short_partition_fraction,
        num_distributors=num_distributors,
        group_size=group_size,
        reserved_per_group=reserved_per_group,
        wfq_weight=weight,
        reserve_cap=reserve_cap,
        probe_window=probe_window,
        dt=dt,
        seed=seed,
    )
    if isinstance(faults, FaultPlan):
        faults = faults.to_schedule(num_workers, num_gms, dt)
    if faults is not None:
        if faults.worker_down.shape != (num_workers,):
            raise ValueError(
                f"fault schedule covers {faults.worker_down.shape[0]} workers, "
                f"simulation has {num_workers} (megha shaves to the GM x LM "
                "grid — build the schedule from grid_workers(num_workers))"
            )
        if rule.needs_grid and faults.gm_down.shape != (num_gms,):
            raise ValueError(
                f"fault schedule covers {faults.gm_down.shape[0]} GMs, "
                f"simulation has {num_gms}"
            )
        if is_empty(faults):
            faults = None  # the no-op schedule: build the plain program
    key = jax.random.PRNGKey(seed)
    match_fn = runtime.default_match_fn(use_pallas=use_pallas)
    # the [W, R] head-of-queue pick wants a 1-row-block kernel tile (queue
    # rows are R <= 64 wide; the wide match's default would pad them 64x)
    pick_fn = runtime.default_match_fn(use_pallas=use_pallas, block_rows=1)
    if telemetry is True:
        telemetry = TelemetryConfig()
    # any registered rule builds and runs through the same three calls
    step = rule.build_step(
        cfg, tasks, key, match_fn=match_fn, pick_fn=pick_fn, faults=faults,
        telemetry=telemetry is not None, provenance=provenance,
    )
    state = rule.init(cfg, tasks)
    if provenance:
        state = (state, init_provenance(tasks.num_tasks))
    cap = max_rounds if max_rounds is not None else estimate_rounds(cfg, tasks)
    if max_rounds is None and faults is not None:
        # outages park work until recovery: extend the horizon past the last
        # finite recovery plus a drain allowance for the re-run tasks
        ups = np.concatenate(
            [np.asarray(faults.worker_up).ravel(), np.asarray(faults.gm_up).ravel()]
        )
        finite = ups[np.isfinite(ups)]
        if finite.size:
            cap += int(math.ceil(float(finite.max()) / dt)) + cfg.heartbeat_rounds
    if until is not None:
        cap = min(cap, int(math.ceil(until / dt)))
    if telemetry is None:
        state = run_to_completion(step, state, chunk=chunk, max_rounds=cap)
        timeline = None
    else:
        state, timeline = run_to_completion_telemetry(
            step, state, telemetry, cfg, tasks,
            faults=faults, chunk=chunk, max_rounds=cap,
        )
    prov = None
    if provenance:
        state, prov = state
    return SimxRun(
        scheduler=name,
        workload_name=workload.name,
        cfg=cfg,
        tasks=tasks,
        state=state,
        timeline=timeline,
        provenance=prov,
    )
