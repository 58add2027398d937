"""Fig. 2 / Fig. 4 sweep drivers: whole grids compiled into ONE program.

The paper's headline comparison sweeps scheduler x load at a fixed DC size
and reports p50/p95 job delay per point.  For the synthetic trace, load
only rescales inter-arrival times (same jobs, same tasks, same durations),
so every grid point shares one ``TaskArrays`` *structure* and differs only
in the ``submit`` / ``job_submit`` arrays — which makes the whole grid a
``jax.vmap`` over (submit-times, seed) of ``simulate_fixed``:

    grid = sweep_grid("megha", cfg, tasks, submit_g, job_submit_g, seeds, R)
    grid["p50"]   # float32[L, S] — one percentile per (load, seed) point

Structural arrays (``job``, ``duration``, ``job_ntasks``, ``job_est``) stay
concrete python-level values: the step builders do numpy work on them
(compact FIFO layouts, partition maps), so they are closed over rather
than vmapped.  Only ``submit``/``job_submit`` and the seed are batched.

Percentiles are reduced *inside* the compiled program — a 50k-worker grid
never materializes per-task records on the host (compare
``SimxRun.to_run_metrics``'s python-loop warning).

``fig4_sweep`` is the fault-tolerance counterpart (paper §3.5, Fig. 4):
the grid axis is fault *severity* instead of load — a batched
``FaultSchedule`` (leading axis = fraction of the DC crashed) vmaps
through ``simulate_fixed`` exactly like the submit-time arrays do, so a
whole availability study is again one compiled program per scheduler.

Both drivers pre-flight the probe/reservation memory the sparrow/eagle
rules materialize per grid point and fail fast with an actionable message
instead of OOMing mid-compile (``check_probe_memory``).  With the capped
per-worker reservation-queue encoding the footprint is O(W * R) carried
state plus O(d * T) static probe-edge constants per point — independent
of the job count for the carried part, and of the same order as the task
arrays for the constants — so the old multi-GiB dense [J, W] ceiling is
retired and the guard only trips on pathological configurations.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from collections.abc import Mapping
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.megha import grid_workers
from repro.simx import engine  # noqa: F401 — registers the rule modules
from repro.simx import runtime
from repro.simx.faults import FaultSchedule, fault_grid_schedule
from repro.simx.runtime import MatchFn, default_match_fn
from repro.simx.state import QueueState, SimxConfig, TaskArrays, export_workload
from repro.workload.synth import synthetic_trace

log = logging.getLogger(__name__)


class _SimulateFixedView(Mapping):
    """Registry-backed view replacing the retired hand-maintained
    ``{scheduler: simulate_fixed}`` dict: ``SIMULATE_FIXED[name]`` is
    ``runtime.simulate_fixed`` bound to the named rule, so registering a
    rule is all it takes to appear here (and in every sweep driver)."""

    def __getitem__(self, name: str) -> Callable:
        # KeyError (not get_rule's ValueError) keeps the Mapping protocol
        # honest: `name in SIMULATE_FIXED` / `.get(name)` work like the
        # plain dict this replaced
        if name.lower() not in runtime.RULES:
            raise KeyError(name)
        return partial(runtime.simulate_fixed, name.lower())

    def __iter__(self):
        return iter(runtime.RULES)

    def __len__(self) -> int:
        return len(runtime.RULES)


#: scheduler name -> round-synchronous simulate_fixed(cfg, tasks, seed, R)
SIMULATE_FIXED: Mapping[str, Callable] = _SimulateFixedView()


def point_summary(
    state,
    tasks: TaskArrays,
    has_queues: Optional[bool] = None,
    provenance=None,
    dt: Optional[float] = None,
) -> dict[str, jax.Array]:
    """Reduce one finished state to the Fig. 2 / Fig. 4 observables, inside
    jit: p50/p95 job delay (Eq. 2; nan-excluding unfinished jobs, via the
    runtime's shared job-delay reduction), completion counts, the
    crash-loss counter, the overhead columns the paper's thesis turns on
    (mean worker utilization, total control messages and probes, megha's
    inconsistency count and its per-task rate), and the reservation-queue
    health counters — a nonzero ``res_overflow`` or ``probe_lag`` flags a
    point whose delays are distorted by a too-small ``reserve_cap`` /
    ``probe_window``.

    ``has_queues`` gates the queue-counter reads (``Rule.has_queues``;
    defaults to the state's class).  Gated reads are ATTRIBUTE reads: a
    renamed counter field raises instead of silently reporting 0 forever.
    Non-queue rules report literal zeros so grid outputs stay homogeneous
    across schedulers.

    ``mean_util`` is exact in closed form — each launched task occupied
    its worker for ``clip(min(finish, t) - start, 0, duration)`` seconds
    (finish was recorded at launch as start + duration), so no per-round
    accumulation is needed: the busy integral divided by ``W * t``.

    ``provenance`` (a ``Provenance``, with ``dt``) adds the delay-breakdown
    columns: per-component nanmeans over completed jobs
    (``mean_<component>``, ``repro.simx.provenance.COMPONENTS``) that sum
    to ``mean`` by construction — the in-jit Fig. 2 counterpart of
    ``SimxRun.delay_decomposition``."""
    if has_queues is None:
        has_queues = isinstance(state, QueueState)
    done = state.task_finish <= state.t
    delays, job_finish = runtime.job_delays_from_state(
        state.task_finish, state.t, tasks
    )
    # min() before the subtraction: an unlaunched task has finish == inf,
    # and min(inf, t) - (inf - d) = -inf clips to 0 without an inf - inf nan
    busy = jnp.clip(
        jnp.minimum(state.task_finish, state.t)
        - (state.task_finish - tasks.duration),
        0.0,
        tasks.duration,
    )
    W = state.worker_finish.shape[0]
    out = {
        "p50": jnp.nanpercentile(delays, 50),
        "p95": jnp.nanpercentile(delays, 95),
        "mean": jnp.nanmean(delays),
        "jobs_done": jnp.sum(jnp.isfinite(job_finish), dtype=jnp.int32),
        "tasks_done": jnp.sum(done, dtype=jnp.int32),
        "lost": state.lost,
        "mean_util": jnp.sum(busy) / (W * jnp.maximum(state.t, 1e-9)),
        "messages": state.messages,
        "probes": state.probes,
        "inconsistencies": state.inconsistencies,
        "inconsistency_rate": state.inconsistencies
        / jnp.float32(max(tasks.num_tasks, 1)),
    }
    if has_queues:
        out["res_overflow"] = state.res_overflow
        out["probe_lag"] = state.probe_lag
    else:
        out["res_overflow"] = jnp.int32(0)
        out["probe_lag"] = jnp.int32(0)
    if provenance is not None:
        from repro.simx.provenance import COMPONENTS, decompose_delays

        if dt is None:
            raise ValueError("point_summary(provenance=...) needs dt")
        comp = decompose_delays(
            provenance, state.task_finish, state.t, tasks, dt
        )
        for key in COMPONENTS:
            out[f"mean_{key}"] = jnp.nanmean(comp[key])
    return out


#: Dense-era [J, W] bytes/element (masks + int32 late-binding
#: intermediates) — kept only so benchmarks/docs can report what the
#: retired encoding *would* have needed.
DENSE_JW_BYTES_PER_ELEM = {"sparrow": 12, "eagle": 18}


def probe_memory_bytes(
    scheduler: str,
    num_jobs: int,
    num_workers: int,
    n_points: int,
    tasks_per_job: int = 1000,
    probe_ratio: int = 2,
    reserve_cap: int = 0,
) -> int:
    """Estimated peak bytes of reservation-queue probe state a compiled
    (vmapped) grid materializes; 0 for schedulers without probes.

    Per point: the carried ``int32[W, R]`` queue plus its per-round
    compaction/scatter intermediates (~3 int32 copies), and the static
    probe-target edge constants, O(d * T) int32 (target table + flat edge
    list) — seed-dependent, so vmapped per point.  Independent of the job
    count except through the edge constants, which scale with the trace
    exactly like the task arrays themselves.
    """
    if scheduler.lower() not in DENSE_JW_BYTES_PER_ELEM:
        return 0
    num_edges = num_jobs * min(probe_ratio * tasks_per_job, num_workers)
    cap = SimxConfig(
        num_workers=num_workers, probe_ratio=probe_ratio, reserve_cap=reserve_cap
    ).queue_cap(num_edges)
    per_point = 12 * num_workers * cap + 8 * num_edges
    return per_point * n_points


def check_probe_memory(
    scheduler: str,
    num_jobs: int,
    num_workers: int,
    n_points: int,
    limit_bytes: Optional[float],
    **kw,
) -> int:
    """Log the reservation-queue memory estimate and fail fast when it
    exceeds ``limit_bytes`` (None disables), instead of OOMing mid-compile.

    With the [W, R] encoding the estimate is MBs where the dense [J, W]
    one was GiBs, so the default ``mem_limit_gb`` ceiling no longer binds
    at paper scale and the guard survives only as a safety valve for
    pathological configurations (huge explicit ``reserve_cap``, enormous
    grids)."""
    est = probe_memory_bytes(scheduler, num_jobs, num_workers, n_points, **kw)
    if not est:
        return est
    log.info(
        "%s grid: ~%.1f MiB reservation-queue state (J=%d, W=%d) "
        "across %d vmapped points",
        scheduler, est / 2**20, num_jobs, num_workers, n_points,
    )
    if limit_bytes is not None and est > limit_bytes:
        raise RuntimeError(
            f"{scheduler} sweep needs ~{est / 2**30:.2f} GiB of "
            f"reservation-queue state (J={num_jobs}, W={num_workers}) over "
            f"{n_points} vmapped grid points, above the "
            f"{limit_bytes / 2**30:.2f} GiB limit. Shrink the grid (fewer "
            "loads/fractions/seeds per call), lower reserve_cap, or raise "
            "mem_limit_gb if the host really has the RAM. megha/pigeon "
            "carry no probe state and sweep at any scale."
        )
    return est


def make_load_grid(
    loads: Sequence[float],
    *,
    num_jobs: int,
    tasks_per_job: int,
    num_workers: int,
    task_duration: float = 1.0,
    seed: int = 0,
    arrivals: str = "poisson",
) -> tuple[TaskArrays, jax.Array, jax.Array]:
    """One synthetic trace per load, stacked along a leading load axis.

    Returns ``(template, submit[L, T], job_submit[L, J])`` — the template
    carries the load-invariant structure (same trace seed => identical
    durations/shapes across loads; only arrival times move).
    """
    template = None
    submit, job_submit = [], []
    for load in loads:
        tasks = export_workload(
            synthetic_trace(
                num_jobs=num_jobs,
                tasks_per_job=tasks_per_job,
                task_duration=task_duration,
                load=load,
                num_workers=num_workers,
                seed=seed,
                arrivals=arrivals,
            )
        )
        if template is None:
            template = tasks
        submit.append(tasks.submit)
        job_submit.append(tasks.job_submit)
    return template, jnp.stack(submit), jnp.stack(job_submit)


def sweep_grid(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    submit_grid: jax.Array,      # float32[L, T]
    job_submit_grid: jax.Array,  # float32[L, J]
    seeds: jax.Array,            # int[S]
    num_rounds: int,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    provenance: bool = False,
    donate: bool = False,
) -> dict[str, jax.Array]:
    """Run the whole (load x seed) grid as one jitted vmap-of-vmap program.

    ``match_fn`` / ``pick_fn`` select the rank-and-select implementations
    (wide match vs. the narrow reservation-queue head pick; see
    ``runtime.default_match_fn`` for the Pallas-vs-jnp choice) — each
    registered rule consumes the one(s) it needs.  Returns
    ``point_summary`` fields stacked to ``[L, S]`` arrays plus the total
    simulated task count (for tasks/sec accounting).  ``provenance=True``
    carries the per-task lifecycle arrays through every point and adds the
    ``mean_<component>`` delay-breakdown columns.

    ``donate=True`` donates the submit/job_submit grid buffers to the
    compiled program (``donate_argnums``), letting XLA reuse their memory
    as scratch — the grids are consumed on the way in, so callers must
    re-stack them before running the same grid again.  Off by default:
    the bench drivers re-run grids from the same host arrays.
    """
    name = scheduler.lower()
    rule = runtime.get_rule(name)  # fail fast on unknown schedulers

    def point(sub, jsub, seed):
        tk = dataclasses.replace(tasks, submit=sub, job_submit=jsub)
        state = runtime.simulate_fixed(
            name, cfg, tk, seed, num_rounds,
            match_fn=match_fn, pick_fn=pick_fn, provenance=provenance,
        )
        prov = None
        if provenance:
            state, prov = state
        return point_summary(
            state, tk, has_queues=rule.has_queues, provenance=prov, dt=cfg.dt
        )

    grid = jax.jit(
        jax.vmap(                     # loads
            jax.vmap(point, in_axes=(None, None, 0)),  # seeds
            in_axes=(0, 0, None),
        ),
        donate_argnums=(0, 1) if donate else (),
    )
    return grid(submit_grid, job_submit_grid, jnp.asarray(seeds))


@dataclasses.dataclass(frozen=True)
class SweepPlan:  # simxlint: disable=PT101 — host-side plan, never traced
    """Everything a Fig. 2 grid run needs, built once: the serial
    ``fig2_sweep`` and the mesh-sharded ``shard.sharded_fig2_sweep`` both
    consume one of these, so their inputs are byte-identical and parity
    between the two paths is a property of the executors alone."""

    name: str
    cfg: SimxConfig
    tasks: TaskArrays
    submit_grid: jax.Array       # float32[L, T]
    job_submit_grid: jax.Array   # float32[L, J]
    seeds: jax.Array             # int[S]
    num_rounds: int
    match_fn: MatchFn | None
    pick_fn: MatchFn | None
    provenance: bool
    annotate: dict               # numpy extras merged into the result


@dataclasses.dataclass(frozen=True)
class FaultPlan:  # simxlint: disable=PT101 — host-side plan, never traced
    """The Fig. 4 counterpart of ``SweepPlan``: one batched
    ``FaultSchedule`` (leading severity axis) instead of submit grids."""

    name: str
    cfg: SimxConfig
    tasks: TaskArrays
    schedules: FaultSchedule     # leaves carry a leading severity axis [F]
    seeds: jax.Array             # int[S]
    num_rounds: int
    match_fn: MatchFn | None
    pick_fn: MatchFn | None
    annotate: dict


def fig2_plan(
    scheduler: str,
    *,
    loads: Sequence[float] = (0.2, 0.5, 0.8),
    num_seeds: int = 3,
    num_workers: int = 10_000,
    num_jobs: int = 200,
    tasks_per_job: int = 1000,
    dt: float = 0.05,
    slack: float = 4.0,
    trace_seed: int = 0,
    use_pallas: bool = False,
    mem_limit_gb: Optional[float] = 16.0,
    provenance: bool = False,
    **cfg_kwargs,
) -> SweepPlan:
    """Build the Fig. 2 grid inputs without running them: the load grid,
    the shared config, and the round budget sized off the slowest point.
    ``fig2_sweep`` executes a plan serially; ``shard.sharded_fig2_sweep``
    executes the same plan across a device mesh."""
    name = scheduler.lower()
    if runtime.get_rule(name).needs_grid:
        num_workers = grid_workers(
            num_workers, cfg_kwargs.get("num_gms", 8), cfg_kwargs.get("num_lms", 8)
        )
    check_probe_memory(
        name, num_jobs, num_workers, len(loads) * num_seeds,
        None if mem_limit_gb is None else mem_limit_gb * 2**30,
        tasks_per_job=tasks_per_job,
        probe_ratio=cfg_kwargs.get("probe_ratio", 2),
        reserve_cap=cfg_kwargs.get("reserve_cap", 0),
    )
    cfg = SimxConfig(num_workers=num_workers, dt=dt, **cfg_kwargs)
    tasks, submit_g, job_submit_g = make_load_grid(
        loads,
        num_jobs=num_jobs,
        tasks_per_job=tasks_per_job,
        num_workers=num_workers,
        seed=trace_seed,
    )
    num_rounds = max(
        engine.estimate_rounds(
            cfg,
            dataclasses.replace(tasks, submit=submit_g[i], job_submit=job_submit_g[i]),
            slack=slack,
        )
        for i in range(len(loads))
    )
    return SweepPlan(
        name=name,
        cfg=cfg,
        tasks=tasks,
        submit_grid=submit_g,
        job_submit_grid=job_submit_g,
        seeds=jnp.arange(num_seeds),
        num_rounds=num_rounds,
        match_fn=default_match_fn(use_pallas=use_pallas),
        pick_fn=default_match_fn(use_pallas=use_pallas, block_rows=1),
        provenance=provenance,
        annotate={
            "loads": np.asarray(loads),
            "num_rounds": np.asarray(num_rounds),
            "num_tasks": np.asarray(tasks.num_tasks),
        },
    )


def fig2_sweep(
    scheduler: str,
    *,
    loads: Sequence[float] = (0.2, 0.5, 0.8),
    num_seeds: int = 3,
    num_workers: int = 10_000,
    num_jobs: int = 200,
    tasks_per_job: int = 1000,
    dt: float = 0.05,
    slack: float = 4.0,
    trace_seed: int = 0,
    use_pallas: bool = False,
    mem_limit_gb: Optional[float] = 16.0,
    provenance: bool = False,
    **cfg_kwargs,
) -> dict[str, np.ndarray]:
    """Convenience wrapper: build the load grid, size the round budget off
    the slowest point, run the compiled grid, return numpy arrays.

    The defaults mirror the paper's synthetic trace (jobs of 1000 one-second
    tasks) at Fig. 2 scale; ``benchmarks/bench_simx.py --full`` drives this
    at 50k workers.  ``use_pallas=True`` runs the rank-and-select match
    as the Pallas kernel (compiled on a TPU, interpreted on the CPU).
    ``mem_limit_gb`` bounds the reservation-queue probe
    state sparrow/eagle grids materialize (fail fast, not mid-compile OOM;
    None disables) — with the O(W * R) encoding it is MBs per point and
    the default ceiling never binds at paper scale.
    """
    plan = fig2_plan(
        scheduler,
        loads=loads, num_seeds=num_seeds, num_workers=num_workers,
        num_jobs=num_jobs, tasks_per_job=tasks_per_job, dt=dt, slack=slack,
        trace_seed=trace_seed, use_pallas=use_pallas,
        mem_limit_gb=mem_limit_gb, provenance=provenance, **cfg_kwargs,
    )
    out = sweep_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, plan.num_rounds,
        match_fn=plan.match_fn, pick_fn=plan.pick_fn,
        provenance=plan.provenance,
    )
    res = {k: np.asarray(v) for k, v in out.items()}
    res.update(plan.annotate)
    return res


def fault_sweep_grid(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    schedules: FaultSchedule,     # leaves carry a leading severity axis [F]
    seeds: jax.Array,             # int[S]
    num_rounds: int,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    donate: bool = False,
) -> dict[str, jax.Array]:
    """Run a (fault severity x seed) grid as one jitted vmap-of-vmap
    program — the Fig. 4 counterpart of ``sweep_grid``.  Returns
    ``point_summary`` fields stacked to ``[F, S]`` arrays (``lost`` counts
    the in-flight tasks crashes destroyed per point).  ``donate=True``
    donates the batched schedule buffers to the program (same contract as
    ``sweep_grid``: the schedule is consumed, rebuild before rerunning)."""
    name = scheduler.lower()
    rule = runtime.get_rule(name)  # fail fast on unknown schedulers

    def point(fs, seed):
        state = runtime.simulate_fixed(
            name, cfg, tasks, seed, num_rounds,
            match_fn=match_fn, pick_fn=pick_fn, faults=fs,
        )
        return point_summary(state, tasks, has_queues=rule.has_queues)

    grid = jax.jit(
        jax.vmap(                     # fault severities
            jax.vmap(point, in_axes=(None, 0)),  # seeds
            in_axes=(0, None),
        ),
        donate_argnums=(0,) if donate else (),
    )
    return grid(schedules, jnp.asarray(seeds))


def fig4_plan(
    scheduler: str,
    *,
    fractions: Sequence[float] = (0.0, 0.02, 0.05, 0.1),
    fail_time: Optional[float] = None,
    outage: float = 2.0,
    gm_outages: int = 0,
    heartbeat_delay: float = 0.0,
    num_seeds: int = 2,
    load: float = 0.8,
    num_workers: int = 1024,
    num_jobs: int = 32,
    tasks_per_job: int = 128,
    dt: float = 0.05,
    slack: float = 6.0,
    trace_seed: int = 0,
    fault_seed: int = 0,
    use_pallas: bool = False,
    mem_limit_gb: Optional[float] = 16.0,
    **cfg_kwargs,
) -> FaultPlan:
    """Build the Fig. 4 grid inputs without running them: the batched
    severity schedule, the trace, and the outage-extended round budget.
    ``fig4_sweep`` executes a plan serially; ``shard.sharded_fig4_sweep``
    executes the same plan across a device mesh."""
    name = scheduler.lower()
    if runtime.get_rule(name).needs_grid:
        num_workers = grid_workers(
            num_workers, cfg_kwargs.get("num_gms", 8), cfg_kwargs.get("num_lms", 8)
        )
    check_probe_memory(
        name, num_jobs, num_workers, len(fractions) * num_seeds,
        None if mem_limit_gb is None else mem_limit_gb * 2**30,
        tasks_per_job=tasks_per_job,
        probe_ratio=cfg_kwargs.get("probe_ratio", 2),
        reserve_cap=cfg_kwargs.get("reserve_cap", 0),
    )
    cfg = SimxConfig(num_workers=num_workers, dt=dt, **cfg_kwargs)
    tasks = export_workload(
        synthetic_trace(
            num_jobs=num_jobs,
            tasks_per_job=tasks_per_job,
            load=load,
            num_workers=num_workers,
            seed=trace_seed,
        )
    )
    if fail_time is None:
        fail_time = 0.5 * float(jnp.max(tasks.submit))
    schedules = fault_grid_schedule(
        num_workers,
        cfg.num_gms,
        fractions,
        fail_time=fail_time,
        outage=outage,
        gm_outages=gm_outages if name == "megha" else 0,
        dt=dt,
        heartbeat_delay=heartbeat_delay,
        seed=fault_seed,
    )
    num_rounds = engine.estimate_rounds(cfg, tasks, slack=slack) + int(
        math.ceil((fail_time + outage) / dt)
    )
    return FaultPlan(
        name=name,
        cfg=cfg,
        tasks=tasks,
        schedules=schedules,
        seeds=jnp.arange(num_seeds),
        num_rounds=num_rounds,
        match_fn=default_match_fn(use_pallas=use_pallas),
        pick_fn=default_match_fn(use_pallas=use_pallas, block_rows=1),
        annotate={
            "fractions": np.asarray(fractions),
            "fail_time": np.asarray(fail_time),
            "outage": np.asarray(outage),
            "num_rounds": np.asarray(num_rounds),
            "num_tasks": np.asarray(tasks.num_tasks),
        },
    )


def fig4_sweep(
    scheduler: str,
    *,
    fractions: Sequence[float] = (0.0, 0.02, 0.05, 0.1),
    fail_time: Optional[float] = None,
    outage: float = 2.0,
    gm_outages: int = 0,
    heartbeat_delay: float = 0.0,
    num_seeds: int = 2,
    load: float = 0.8,
    num_workers: int = 1024,
    num_jobs: int = 32,
    tasks_per_job: int = 128,
    dt: float = 0.05,
    slack: float = 6.0,
    trace_seed: int = 0,
    fault_seed: int = 0,
    use_pallas: bool = False,
    mem_limit_gb: Optional[float] = 16.0,
    **cfg_kwargs,
) -> dict[str, np.ndarray]:
    """The Fig. 4 availability study: one compiled (severity x seed) grid.

    Each severity point crashes ``fraction * num_workers`` random workers
    at ``fail_time`` (default: mid-arrival-span) for ``outage`` seconds —
    plus, for megha, ``gm_outages`` GMs over the same window and an
    optional heartbeat-delay perturbation.  The qualitative signature to
    expect mirrors the paper's §3.5 claim: megha's eventually-consistent
    state absorbs the crashes (stale views are repaired by the normal
    inconsistency/heartbeat machinery), while pigeon's static groups park
    work behind dead workers until they return.
    """
    plan = fig4_plan(
        scheduler,
        fractions=fractions, fail_time=fail_time, outage=outage,
        gm_outages=gm_outages, heartbeat_delay=heartbeat_delay,
        num_seeds=num_seeds, load=load, num_workers=num_workers,
        num_jobs=num_jobs, tasks_per_job=tasks_per_job, dt=dt, slack=slack,
        trace_seed=trace_seed, fault_seed=fault_seed, use_pallas=use_pallas,
        mem_limit_gb=mem_limit_gb, **cfg_kwargs,
    )
    out = fault_sweep_grid(
        plan.name, plan.cfg, plan.tasks, plan.schedules, plan.seeds,
        plan.num_rounds, match_fn=plan.match_fn, pick_fn=plan.pick_fn,
    )
    res = {k: np.asarray(v) for k, v in out.items()}
    res.update(plan.annotate)
    return res
