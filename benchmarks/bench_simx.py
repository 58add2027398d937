"""Events vs. simx backend throughput: tasks/sec per sweep point.

Two sections:

1. **Point ladder** (megha) — scheduling throughput (tasks simulated per
   wall-clock second) of the pure-Python event loop vs. the compiled
   round-stepped backend on the same load-0.8 synthetic trace at
   1k / 4k / 16k (``--full``: + 50k) workers.  The trace holds the arrival
   span fixed (~12 s of simulated time), so the task count scales with DC
   size exactly like a Fig. 2 sweep point: events cost scales with the
   task count, simx with the round count (span / dt) — the bigger the DC,
   the wider the gap.  simx rows are timed warm (the compiled program is
   the artifact a sweep reuses across its whole grid); the one-off compile
   wall-clock is reported alongside.  Two round lengths are reported:
   dt=0.05 (the engine default, 5% of the 1 s task duration) and dt=0.1
   (coarser quantization, ~2x the throughput — fine for relative sweeps).

2. **Fig. 2 grid** (every registered rule — the four paper schedulers
   plus the omniscient oracle) — the ``repro.simx.sweep`` driver compiles
   a whole (seed x load) grid into ONE vmapped program per scheduler and
   reports aggregate tasks/sec over the grid plus the highest-load p50
   job delay.  Default is a small CI-sized grid; ``--full`` runs the
   paper-scale grid — 50k workers, jobs of 1000 one-second tasks
   (Table 1's synthetic trace) — and takes hours on CPU (see
   docs/fig2_sweep.md for expected runtimes and how to read the output
   against the paper's plots).

3. **Fig. 4 fault grid** — the default grid always carries one
   ``simx_fig4_smoke`` row (a tiny megha severity grid, so the fault path
   can't silently rot in CI); ``--faults`` adds the full
   (fraction x seed) availability grid for every registered rule
   (``repro.simx.sweep.fig4_sweep``; recipe in docs/fig4_faults.md).
   ``--only-faults`` (module CLI) prints just the fault rows — the CI
   smoke entrypoint.  Two more always-on rows: ``simx_oracle_gap``
   (``--only-oracle``) reports each scheduler's p50/p95 partial-knowledge
   gap vs the omniscient-oracle lower bound on a shared grid point, and
   ``simx_doneprobe`` records the dispatch overhead saved by returning
   the chunk runner's all-done flag from inside the jitted chunk.

4. **J-heavy queue-encoding rows** — one sparrow + one eagle point at
   32768 jobs x 50k workers, a (jobs, workers) product whose dense
   [J, W] probe state (~20 / ~30 GiB) tripped the retired
   ``check_probe_memory`` 16 GiB ceiling; the capped per-worker
   reservation-queue encoding carries ~2 MB of scan state instead.
   Rows record tasks/sec, measured carried-state bytes (summed scan-carry
   leaves), the dense-era GiB figure, and the overflow counter.  Runs
   with ``--full`` (50k-worker compiles cost minutes, like the rest of
   that tier); ``--only-bigjob`` prints just these rows.

5. **Telemetry traces** (``--trace``; ``--only-trace`` is the CI smoke
   entrypoint) — one telemetry+provenance run per registered rule on a
   shared tiny trace, written as a combined Chrome-trace JSON (one
   process per rule: counter tracks from the Timeline PLUS per-task
   wait/run duration spans from the provenance arrays — load it in
   ``chrome://tracing`` or Perfetto) plus one bench row per rule
   carrying the control-plane overhead counters.

6. **Delay breakdown** (``--breakdown``; ``--only-breakdown`` is the CI
   smoke entrypoint) — the oracle-gap point rerun with the provenance
   stage on (``repro.simx.provenance``): one row per registered rule
   splitting its mean job delay into eligible-wait / placement-wait /
   inconsistency-retry / fault-rework, plus the per-component gap vs the
   omniscient oracle — *why* each architecture trails the lower bound,
   not just by how much (recipe: docs/observability.md).

7. **Steady-state rows** (``--steady``; ``--only-steady`` is the CI
   smoke entrypoint) — the streaming engine (``repro.simx.stream``)
   driven open-loop: per scheduler, sketch-estimated p99/p999 JCT-delay
   tail and exact busy-seconds utilization at each offered load (Poisson
   arrivals through the ring-buffer window), plus one overload ->
   recovery transient (``PhasedArrivals`` bursting past capacity)
   recording the peak pending backlog and that it drains.  The smoke tier
   runs megha / sparrow / oracle; ``--full`` runs every registered rule
   at more loads.  Recipe and how to read the rows: docs/steady_state.md.

8. **Mesh-sharded rows** (``--sharded``; ``--only-sharded`` is the CI
   smoke entrypoint) — the ``repro.simx.shard`` drivers: the Fig. 2 grid
   and a steady-state load pair run once on a 1-device mesh and once
   across every visible device, recording ``n_devices``, warm per-device
   wall time, and the measured scaling efficiency vs the 1-device path.
   CI forces 8 CPU devices (``XLA_FLAGS=--xla_force_host_platform_
   device_count=8``) on one physical core, so the recorded efficiency
   there measures partitioning overhead, not speedup — on real
   multi-chip hosts the same rows show the scale-out.  Recipe:
   docs/sharded_sweeps.md.

9. **Donation row** (always on) — ``simx_donation`` times the megha
   chunk runner and a small sweep grid with and without buffer donation
   (``donate_argnums``) and records the wall deltas plus the compiled
   programs' temp-memory figures where XLA reports them.

Every invocation also merges its rows into ``BENCH_simx.json`` — a JSON
array keyed by (git rev, bench name), the machine-readable trajectory
that makes speed/overhead regressions diffable across PRs (disable with
``--bench-json none``).  Unless ``--no-compile-cache`` is passed, the
persistent JAX compilation cache is enabled (``JAX_COMPILATION_CACHE_DIR``
or ``<checkout>/.jax_compile_cache``, ``repro.compile_cache``) so bench
reruns and CI smoke steps stop
paying the per-rule recompile; the point-ladder rows report
``compile_s`` (cold, first build) next to ``compile_warm_s`` (a fresh
AOT build of the same program, which hits the persistent cache).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.sim.simulator import run_simulation
from repro.simx import engine as sxe
from repro.simx import megha as sxm
from repro.simx import sweep as sxs
from repro.simx.state import SimxConfig, export_workload, init_megha_state
from repro.workload.synth import synthetic_trace

DC_SIZES = (1024, 4096, 16384)
DC_SIZES_FULL = (1024, 4096, 16384, 50_000)
SPAN = 12.0      # seconds of simulated arrivals per sweep point
TASKS_PER_JOB = 128
LOAD = 0.8

#: (seed x load) grid shapes for section 2.
SWEEP = dict(
    loads=(0.4, 0.8), num_seeds=2, num_workers=1024, num_jobs=32,
    tasks_per_job=128, dt=0.05,
)
SWEEP_FULL = dict(
    loads=(0.2, 0.5, 0.8), num_seeds=2, num_workers=50_000, num_jobs=480,
    tasks_per_job=1000, dt=0.05,
)

#: Fig. 4 (fraction x seed) fault-severity grid shapes.
FAULTS = dict(
    fractions=(0.0, 0.1, 0.25), num_seeds=1, num_workers=256, num_jobs=16,
    tasks_per_job=64, outage=2.0, gm_outages=1, dt=0.05,
)
FAULTS_FULL = dict(
    fractions=(0.0, 0.05, 0.1, 0.2), num_seeds=2, num_workers=10_000,
    num_jobs=100, tasks_per_job=500, outage=5.0, gm_outages=2, dt=0.05,
)

#: Mesh-sharded fig2 grid shapes for section 8 (``--sharded``): small
#: enough to compile fast under 8 forced CPU devices, uneven on purpose
#: (15 and 24 points) so the pad-and-mask path is always exercised.
SHARDED = dict(
    loads=(0.35, 0.55, 0.7, 0.85, 0.95), num_seeds=3, num_workers=64,
    num_jobs=6, tasks_per_job=8, dt=0.05, num_gms=2, num_lms=2,
)
SHARDED_FULL = dict(
    loads=(0.2, 0.4, 0.6, 0.8, 0.9, 0.95), num_seeds=4, num_workers=1024,
    num_jobs=32, tasks_per_job=64, dt=0.05,
)
#: Steady-state lane batch for the ``simx_steady_sharded`` row.
SHARDED_STEADY = dict(
    num_workers=64, loads=(0.5, 0.9), num_jobs=24, tasks_per_job=8,
    window_jobs=16, window_tasks=128, rounds_per_refill=16,
    num_gms=2, num_lms=2,
)

#: This invocation's machine-readable rows (mirrors the printed CSV).
_BENCH_ROWS: list[dict] = []


def _record(name: str, us: float, **derived) -> str:
    """Record one bench row: append the machine-readable dict to the
    ``BENCH_simx.json`` trajectory buffer and return the human CSV line
    the bench harness prints (``name,us_per_call,k=v;k=v``)."""
    _BENCH_ROWS.append(
        {"name": name, "us_per_call": round(float(us), 3), **derived}
    )
    txt = ";".join(f"{k}={v}" for k, v in derived.items())
    return f"{name},{us:.2f},{txt}"


def _git_rev() -> str:
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, check=True, timeout=10,
        ).stdout.strip()
        return out or "unknown"
    except Exception:
        return "unknown"


def write_bench_json(rows: list[dict], path: str = "BENCH_simx.json") -> None:
    """Merge this invocation's rows into the append-style trajectory file:
    a JSON array of rows keyed by (git rev, bench name).  Re-running a
    bench at the same rev replaces its row; other revs' rows are kept, so
    the file accumulates the across-PR trajectory ``benchmarks/run.py``
    and CI diff.  A missing or corrupt file is treated as empty."""
    import json

    rev = _git_rev()
    stamped = [{"rev": rev, **r} for r in rows]
    try:
        with open(path) as f:
            existing = json.load(f)
        if not isinstance(existing, list):
            existing = []
    except (FileNotFoundError, json.JSONDecodeError):
        existing = []
    fresh = {(r["rev"], r["name"]) for r in stamped}
    merged = [
        r for r in existing if (r.get("rev"), r.get("name")) not in fresh
    ] + stamped
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)
        f.write("\n")


def _trace(workers: int):
    jobs = max(8, int(LOAD * workers * SPAN / TASKS_PER_JOB))
    return synthetic_trace(
        num_jobs=jobs,
        tasks_per_job=TASKS_PER_JOB,
        load=LOAD,
        num_workers=workers,
        seed=13,
    )


def _simx_point(wl, workers: int, dt: float) -> dict:
    cfg = SimxConfig(num_workers=(workers // 64) * 64, dt=dt)
    tasks = export_workload(wl)
    orders = sxm.gm_orders(jax.random.PRNGKey(0), cfg)
    step = sxm.make_megha_step(cfg, tasks, orders)
    state0 = init_megha_state(cfg, tasks.num_tasks)
    cap = sxe.estimate_rounds(cfg, tasks)
    runner = sxe.make_chunk_runner(step, chunk=32)  # returns (state, done)
    t0 = time.time()
    jax.block_until_ready(runner(state0))
    compile_wall = time.time() - t0
    # warm AOT rebuild of the same program: re-lowers and recompiles from
    # scratch in-process, so with the persistent compile cache enabled
    # this times a cache hit (and without it, a full recompile)
    t0 = time.time()
    runner.lower(state0).compile()
    compile_warm = time.time() - t0
    t0 = time.time()
    state = sxe.run_to_completion(
        step, state0, chunk=32, max_rounds=cap, runner=runner
    )
    wall = time.time() - t0
    done = int((state.task_finish <= state.t).sum())
    return {
        "wall": wall, "compile": compile_wall,
        "compile_warm": compile_warm, "done": done,
    }


def _sweep_rows(full: bool) -> list[str]:
    """Section 2: the vmap-compiled Fig. 2 grid, one row per scheduler."""
    spec = SWEEP_FULL if full else SWEEP
    rows = []
    grid_pts = len(spec["loads"]) * spec["num_seeds"]
    for sched in sxe.SCHEDULERS:
        t0 = time.time()
        r = sxs.fig2_sweep(sched, **spec)
        wall = time.time() - t0
        total = int(r["num_tasks"]) * grid_pts
        done = int(np.sum(r["tasks_done"]))
        p50_top = float(np.mean(r["p50"][-1]))  # highest load, seed-averaged
        derived = dict(
            tasks_per_sec=round(total / wall),
            wall_s=round(wall, 2),
            grid=f"{len(spec['loads'])}x{spec['num_seeds']}",
            rounds=int(r["num_rounds"]),
            done=f"{done}/{total}",
            messages=int(np.sum(r["messages"])),
            probes=int(np.sum(r["probes"])),
            mean_util=round(float(np.mean(r["mean_util"])), 4),
        )
        derived[f"p50_load{spec['loads'][-1]:g}"] = round(p50_top, 3)
        if sched == "megha":
            derived["inconsistencies"] = int(np.sum(r["inconsistencies"]))
        rows.append(
            _record(f"simx_fig2_{sched}", wall * 1e6 / max(total, 1), **derived)
        )
    return rows


def _fault_rows(full: bool, schedulers=None) -> list[str]:
    """Section 3: one vmapped (fraction x seed) Fig. 4 grid per scheduler."""
    if schedulers is None:
        schedulers = sxe.SCHEDULERS  # resolve the live registry at call time
    spec = dict(FAULTS_FULL if full else FAULTS)
    gm_outages = spec.pop("gm_outages")
    megha_kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0)
    rows = []
    grid_pts = len(spec["fractions"]) * spec["num_seeds"]
    for sched in schedulers:
        t0 = time.time()
        r = sxs.fig4_sweep(
            sched,
            gm_outages=gm_outages if sched == "megha" else 0,
            **spec,
            **(megha_kw if sched == "megha" else {}),
        )
        wall = time.time() - t0
        total = int(r["num_tasks"]) * grid_pts
        done = int(np.sum(r["tasks_done"]))
        p95 = r["p95"].mean(axis=1)  # seed-averaged per fraction
        derived = dict(
            tasks_per_sec=round(total / wall),
            wall_s=round(wall, 2),
            grid=f"{len(spec['fractions'])}x{spec['num_seeds']}",
            done=f"{done}/{total}",
            lost_top=int(np.sum(r["lost"][-1])),
            messages=int(np.sum(r["messages"])),
            p95_f0=round(float(p95[0]), 3),
        )
        derived[f"p95_f{spec['fractions'][-1]:g}"] = round(float(p95[-1]), 3)
        if sched == "megha":
            derived["inconsistencies"] = int(np.sum(r["inconsistencies"]))
        rows.append(
            _record(f"simx_fig4_{sched}", wall * 1e6 / max(total, 1), **derived)
        )
    return rows


#: Section 4: jobs x workers sized so the dense [J, W] encoding needed
#: 12 * J * W ~ 20 GiB (sparrow) / 18 * J * W ~ 30 GiB (eagle) for ONE
#: point — above the old 16 GiB fail-fast ceiling — while the task count
#: (and hence the round budget) stays bench-sized.
BIGJOB = dict(num_jobs=32768, tasks_per_job=2, num_workers=50_000)


def _bigjob_rows() -> list[str]:
    """Section 4: the J-heavy grid point the dense encoding could not run."""
    import jax.tree_util as jtu

    from repro.simx import sparrow as sxsp
    from repro.simx import eagle as sxea
    from repro.simx.state import init_eagle_state, init_sparrow_state

    spec = BIGJOB
    rows = []
    for sched, sim, init in (
        ("sparrow", sxsp.simulate_fixed, init_sparrow_state),
        ("eagle", sxea.simulate_fixed, init_eagle_state),
    ):
        dense_gb = (
            sxs.DENSE_JW_BYTES_PER_ELEM[sched]
            * spec["num_jobs"] * spec["num_workers"] / 2**30
        )
        assert dense_gb > 16, "point must exceed the retired dense ceiling"
        # the queue-model pre-flight that replaced that ceiling passes
        sxs.check_probe_memory(
            sched, spec["num_jobs"], spec["num_workers"], 1, 16 * 2**30,
            tasks_per_job=spec["tasks_per_job"],
        )
        cfg = SimxConfig(num_workers=spec["num_workers"], dt=0.05)
        tasks = export_workload(synthetic_trace(
            num_jobs=spec["num_jobs"], tasks_per_job=spec["tasks_per_job"],
            load=0.8, num_workers=spec["num_workers"], seed=13,
        ))
        state_bytes = sum(
            x.nbytes for x in jtu.tree_leaves(init(cfg, tasks))
        )
        rounds = sxe.estimate_rounds(cfg, tasks)
        t0 = time.time()
        state = jax.block_until_ready(sim(cfg, tasks, 0, rounds))
        wall = time.time() - t0
        done = int((state.task_finish <= state.t).sum())
        rows.append(_record(
            f"simx_bigjob_{sched}", wall * 1e6 / tasks.num_tasks,
            tasks_per_sec=round(tasks.num_tasks / wall),
            wall_s=round(wall, 2),
            jobs=spec["num_jobs"],
            workers=spec["num_workers"],
            rounds=rounds,
            done=f"{done}/{tasks.num_tasks}",
            state_mb=round(state_bytes / 2**20, 1),
            dense_gb=round(dense_gb, 1),
            overflow=int(state.res_overflow),
            lag=int(state.probe_lag),
        ))
    return rows


def _doneprobe_row() -> list[str]:
    """Satellite record: ``make_chunk_runner`` now returns its all-done
    flag from inside the jitted chunk, so ``run_to_completion``'s host
    loop reads one ready scalar instead of dispatching a second device
    program (``jnp.all``) per chunk.  This row times both probe styles on
    the same compiled chunk runner (µs per chunk, warm)."""
    import jax.numpy as jnp

    from repro.simx.state import init_megha_state as init

    wl = synthetic_trace(
        num_jobs=16, tasks_per_job=64, load=0.8, num_workers=1024, seed=13
    )
    cfg = SimxConfig(num_workers=1024, dt=0.05)
    tasks = export_workload(wl)
    orders = sxm.gm_orders(jax.random.PRNGKey(0), cfg)
    step = sxm.make_megha_step(cfg, tasks, orders)
    state0 = init(cfg, tasks.num_tasks)
    runner = sxe.make_chunk_runner(step, chunk=8)
    probe = jax.jit(lambda s: jnp.all(s.task_finish <= s.t))
    s, d = runner(state0)
    jax.block_until_ready((s, d))
    bool(probe(s))  # warm both programs
    # isolate the probe itself (the chunk advance is identical either
    # way): run the chunks first and probe FRESH device arrays — a jax
    # scalar caches its host value after the first bool(), so re-reading
    # one flag would time a Python attribute lookup, not the transfer
    reps = 100
    states, flags = [], []
    s = state0
    for _ in range(reps):
        s, d = runner(s)
        states.append(s)
        flags.append(d)
    jax.block_until_ready(flags)
    t0 = time.time()
    for d in flags:
        bool(d)                      # fused: one scalar transfer per chunk
    fused = (time.time() - t0) / reps
    t0 = time.time()
    for s in states:
        bool(probe(s))               # retired: second dispatch per chunk
    two = (time.time() - t0) / reps
    return [_record(
        "simx_doneprobe", fused * 1e6,
        fused_probe_us_per_chunk=round(fused * 1e6, 1),
        second_dispatch_us_per_chunk=round(two * 1e6, 1),
        saved_us_per_chunk=round(max(two - fused, 0.0) * 1e6, 1),
    )]


#: The oracle-gap smoke grid: one shared (load x seed) point, small enough
#: for every PR, queueing-dominated enough for a visible gap.
ORACLE_GAP = dict(
    loads=(0.8,), num_seeds=1, num_workers=256, num_jobs=16,
    tasks_per_job=64, dt=0.05,
)


def _oracle_gap_row() -> list[str]:
    """The always-on oracle smoke: p50/p95 partial-knowledge gap of megha
    and sparrow vs the omniscient-oracle lower bound on one shared grid
    point — the paper's Fig. 2 argument as a per-PR number (and the CI
    guarantee that the oracle rule keeps compiling)."""
    t0 = time.time()
    oracle = sxs.fig2_sweep("oracle", **ORACLE_GAP)
    megha = sxs.fig2_sweep(
        "megha", num_gms=4, num_lms=4, heartbeat_interval=1.0, **ORACLE_GAP
    )
    sparrow = sxs.fig2_sweep("sparrow", **ORACLE_GAP)
    wall = time.time() - t0
    o50, o95 = float(oracle["p50"][0, 0]), float(oracle["p95"][0, 0])
    done = int(np.sum(oracle["tasks_done"]))
    return [_record(
        "simx_oracle_gap", wall,
        oracle_p50=round(o50, 3),
        oracle_p95=round(o95, 3),
        megha_gap_p50=round(float(megha["p50"][0, 0]) - o50, 3),
        megha_gap_p95=round(float(megha["p95"][0, 0]) - o95, 3),
        sparrow_gap_p50=round(float(sparrow["p50"][0, 0]) - o50, 3),
        sparrow_gap_p95=round(float(sparrow["p95"][0, 0]) - o95, 3),
        done=f"{done}/{int(oracle['num_tasks'])}",
    )]


def _fault_smoke_row() -> list[str]:
    """The always-on smoke: a minimal megha severity grid exercising the
    fault path (crash wave + GM window + recovery) end to end."""
    t0 = time.time()
    r = sxs.fig4_sweep(
        "megha", fractions=(0.0, 0.2), num_seeds=1, num_workers=128,
        num_jobs=8, tasks_per_job=32, outage=1.5, gm_outages=1, dt=0.05,
        num_gms=4, num_lms=4, heartbeat_interval=1.0,
    )
    wall = time.time() - t0
    done = int(np.sum(r["tasks_done"]))
    total = 2 * int(r["num_tasks"])
    derived = dict(
        wall_s=round(wall, 2),
        done=f"{done}/{total}",
        lost=int(np.sum(r["lost"])),
    )
    derived["p95_f0.2"] = round(float(r["p95"][-1, 0]), 3)
    return [_record("simx_fig4_smoke", wall * 1e6 / total, **derived)]


#: The --trace grid: one tiny telemetry-enabled run per registered rule.
TRACE = dict(num_jobs=16, tasks_per_job=64, load=0.8, num_workers=256, seed=13)


def _trace_rows(trace_out: str = "simx_trace.json") -> list[str]:
    """Section 5 (``--trace``): run every registered rule with telemetry +
    provenance on a shared tiny trace, write the combined Chrome-trace
    JSON (per rule one process holding the counter tracks AND the
    per-task wait/run duration spans; loads in ``chrome://tracing`` /
    Perfetto), and record one overhead row per rule."""
    import json

    from repro.simx.telemetry import TelemetryConfig

    wl = synthetic_trace(**TRACE)
    tel = TelemetryConfig(stride=4)
    megha_kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0)
    events: list[dict] = []
    rows = []
    for pid, sched in enumerate(sxe.SCHEDULERS, start=1):
        t0 = time.time()
        run = sxe.simulate_workload(
            sched, wl, TRACE["num_workers"], telemetry=tel, provenance=True,
            **(megha_kw if sched == "megha" else {}),
        )
        wall = time.time() - t0
        tl = run.timeline
        events.extend(
            tl.to_chrome_trace(pid=pid, process_name=f"simx:{sched}")["traceEvents"]
        )
        # per-task lifecycle spans on the same pid; the counter trace
        # already named the process, so drop the duplicate metadata
        spans = [
            e for e in run.span_events(pid=pid) if e["name"] != "process_name"
        ]
        events.extend(spans)
        series = {k: np.asarray(v) for k, v in tl.series.items()}
        derived = dict(
            wall_s=round(wall, 2),
            samples=tl.num_samples,
            stride=tl.stride,
            spans=sum(1 for e in spans if e["ph"] == "X"),
            launches=int(series["launches"].sum()),
            messages=int(run.state.messages),
            probes=int(run.state.probes),
            peak_util=round(float(series["utilization"].max()), 4),
        )
        if sched == "megha":
            derived["inconsistencies"] = int(run.state.inconsistencies)
            derived["view_repairs"] = int(series["view_repairs"].sum())
        rows.append(_record(f"simx_trace_{sched}", wall * 1e6, **derived))
    with open(trace_out, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return rows


def _breakdown_rows() -> list[str]:
    """Section 6 (``--breakdown``): the oracle-gap point with the
    provenance stage on — one row per rule splitting the mean job delay
    into the four components and attributing the oracle gap to them."""
    from repro.simx.provenance import COMPONENTS

    megha_kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0)
    results: dict[str, dict] = {}
    walls: dict[str, float] = {}
    for sched in sxe.SCHEDULERS:
        t0 = time.time()
        results[sched] = sxs.fig2_sweep(
            sched, provenance=True,
            **(megha_kw if sched == "megha" else {}), **ORACLE_GAP,
        )
        walls[sched] = time.time() - t0
    oracle = results["oracle"]
    rows = []
    for sched in sxe.SCHEDULERS:
        r = results[sched]
        derived = dict(
            wall_s=round(walls[sched], 2),
            mean=round(float(r["mean"][0, 0]), 3),
        )
        for k in COMPONENTS:
            derived[k] = round(float(r[f"mean_{k}"][0, 0]), 4)
        if sched != "oracle":
            derived["gap"] = round(
                float(r["mean"][0, 0]) - float(oracle["mean"][0, 0]), 3
            )
            for k in COMPONENTS:
                derived[f"gap_{k}"] = round(
                    float(r[f"mean_{k}"][0, 0])
                    - float(oracle[f"mean_{k}"][0, 0]),
                    4,
                )
        rows.append(_record(
            f"simx_breakdown_{sched}", walls[sched] * 1e6, **derived
        ))
    return rows


#: Section 7: the steady-state streaming grid (smoke / --full tiers).
def _donation_row() -> list[str]:
    """Section 9: buffer-donation deltas.  Times the megha chunk runner
    and a small fig2 sweep grid with and without ``donate_argnums`` on
    the carried state / grid buffers, and records the XLA-reported
    temp-allocation sizes where the backend exposes them.  On CPU,
    donation is typically a no-op (XLA ignores the aliasing hint), so
    the row mostly documents that the knob is wired and free."""
    import warnings

    from repro.simx.state import init_megha_state as _init

    workers = 256
    wl = _trace(workers)
    cfg = SimxConfig(num_workers=workers, dt=0.05)
    tasks = export_workload(wl)
    step = sxm.make_megha_step(
        cfg, tasks, sxm.gm_orders(jax.random.PRNGKey(0), cfg)
    )
    cap = sxe.estimate_rounds(cfg, tasks)
    derived: dict = {}
    walls: dict = {}
    with warnings.catch_warnings():
        # CPU backends warn that donated buffers were not usable
        warnings.simplefilter("ignore")
        for tag, donate in (("nodonate", False), ("donate", True)):
            runner = sxe.make_chunk_runner(step, chunk=32, donate=donate)
            jax.block_until_ready(runner(_init(cfg, tasks.num_tasks)))
            t0 = time.time()
            sxe.run_to_completion(
                step, _init(cfg, tasks.num_tasks), chunk=32,
                max_rounds=cap, runner=runner,
            )
            walls[tag] = time.time() - t0
            derived[f"wall_{tag}_s"] = round(walls[tag], 3)
            try:
                mem = (
                    runner.lower(_init(cfg, tasks.num_tasks))
                    .compile().memory_analysis()
                )
                derived[f"temp_mb_{tag}"] = round(
                    mem.temp_size_in_bytes / 2**20, 2
                )
            except Exception:
                derived[f"temp_mb_{tag}"] = "na"
        # the vmapped sweep grid: donated submit/job_submit grids.  A
        # fresh plan per run — donation consumes the grid buffers.
        sweep_spec = dict(
            loads=(0.4, 0.8), num_seeds=2, num_workers=256, num_jobs=8,
            tasks_per_job=16, dt=0.05,
        )
        for tag, donate in (("nodonate", False), ("donate", True)):
            plan = sxs.fig2_plan("megha", **sweep_spec)
            t0 = time.time()
            jax.block_until_ready(sxs.sweep_grid(
                plan.name, plan.cfg, plan.tasks, plan.submit_grid,
                plan.job_submit_grid, plan.seeds, plan.num_rounds,
                match_fn=plan.match_fn, pick_fn=plan.pick_fn, donate=donate,
            ))
            derived[f"sweep_wall_{tag}_s"] = round(time.time() - t0, 3)
    saved = walls["nodonate"] - walls["donate"]
    derived["wall_delta_pct"] = round(100.0 * saved / max(walls["nodonate"], 1e-9), 1)
    return [_record("simx_donation", walls["donate"] * 1e6, **derived)]


def _sharded_rows(full: bool = False) -> list[str]:
    """Section 8 (``--sharded``): the mesh-sharded drivers against their
    single-device selves.  One ``fig2_plan`` per scheduler feeds both a
    1-device and an all-devices ``sharded_grid_program`` (identical
    inputs, identical outputs — parity is pinned by
    ``tests/test_simx_shard.py``); the row records the device count, the
    warm per-sweep walls, and ``scaling_efficiency = wall_1dev /
    (n_devices * wall_ndev)`` — ~1.0 means perfect scaling on real
    device fleets, ~1/n on the 1-physical-core CI hosts that force 8
    virtual CPU devices.  A ``simx_steady_sharded`` row does the same
    for the lane-batched steady-state driver."""
    from repro.simx import shard as sxsh
    from repro.simx.stream import run_steady_state
    from repro.workload.synth import PoissonArrivals, fixed_job_factory

    spec = dict(SHARDED_FULL if full else SHARDED)
    schedulers = sxe.SCHEDULERS if full else ("megha", "sparrow")
    n_dev = jax.device_count()
    rows = []
    for sched in schedulers:
        plan = sxs.fig2_plan(sched, **spec)
        pts = len(spec["loads"]) * spec["num_seeds"]
        walls = {}
        for nd in dict.fromkeys((1, n_dev)):  # 1 first; dedup if n_dev == 1
            prog = sxsh.sharded_grid_program(
                plan.name, plan.cfg, plan.tasks, plan.submit_grid,
                plan.job_submit_grid, plan.seeds, plan.num_rounds,
                mesh=sxsh.sweep_mesh(nd),
                match_fn=plan.match_fn, pick_fn=plan.pick_fn,
            )
            t0 = time.time()
            jax.block_until_ready(prog())
            cold = time.time() - t0
            t0 = time.time()
            jax.block_until_ready(prog())
            walls[nd] = (cold, time.time() - t0)
        warm1 = walls[1][1]
        cold_n, warm_n = walls[n_dev]
        rows.append(_record(
            f"simx_fig2_sharded_{sched}", warm_n * 1e6 / pts,
            n_devices=n_dev,
            wall_s=round(warm_n, 3),
            wall_1dev_s=round(warm1, 3),
            compile_s=round(max(cold_n - warm_n, 0.0), 3),
            scaling_efficiency=round(warm1 / max(n_dev * warm_n, 1e-9), 3),
            grid=f"{len(spec['loads'])}x{spec['num_seeds']}",
            rounds=int(plan.annotate["num_rounds"]),
        ))
    # lane-batched steady state: serial per-load runs vs one mesh batch.
    # Arrival processes are single-use generators — build fresh ones per
    # driver via the factory.
    st = SHARDED_STEADY
    demand = float(st["tasks_per_job"])
    kw = dict(
        window_jobs=st["window_jobs"], window_tasks=st["window_tasks"],
        rounds_per_refill=st["rounds_per_refill"],
        num_gms=st["num_gms"], num_lms=st["num_lms"],
    )

    def mk(load):
        return PoissonArrivals(
            rate=load * st["num_workers"] / demand,
            job_factory=fixed_job_factory(st["tasks_per_job"], 1.0),
            seed=7, num_jobs=st["num_jobs"],
        )

    t0 = time.time()
    serial = [
        run_steady_state("megha", mk(ld), st["num_workers"], **kw)
        for ld in st["loads"]
    ]
    wall_serial = time.time() - t0
    t0 = time.time()
    batched = sxsh.sharded_steady_state(
        "megha", [mk(ld) for ld in st["loads"]], st["num_workers"],
        mesh=sxsh.sweep_mesh(min(n_dev, len(st["loads"]))), **kw,
    )
    wall_sharded = time.time() - t0
    done = sum(r.tasks_completed for r in batched)
    total = sum(r.tasks_admitted for r in serial)
    rows.append(_record(
        "simx_steady_sharded", wall_sharded * 1e6 / max(total, 1),
        n_devices=n_dev,
        lanes=len(st["loads"]),
        wall_s=round(wall_sharded, 3),
        wall_serial_s=round(wall_serial, 3),
        scaling_efficiency=round(
            wall_serial / max(len(st["loads"]) * wall_sharded, 1e-9), 3
        ),
        done=f"{done}/{total}",
        p999_top=round(
            float(batched[-1].quantile(0.999)), 3
        ),
    ))
    return rows


STEADY = dict(
    num_workers=256, loads=(0.5, 0.9), schedulers=("megha", "sparrow", "oracle"),
    num_jobs=96, tasks_per_job=8, window_jobs=80, window_tasks=640,
    rounds_per_refill=16,
)
STEADY_FULL = dict(
    num_workers=1024, loads=(0.3, 0.6, 0.9), schedulers=None,  # all rules
    num_jobs=512, tasks_per_job=16, window_jobs=160, window_tasks=2560,
    rounds_per_refill=32,
)


def _steady_rows(full: bool = False) -> list[str]:
    """Section 7 (``--steady``): stream open-loop Poisson arrivals through
    the ring-buffer window at each offered load and report the in-jit
    sketch's p99/p999 delay estimates + exact busy-seconds utilization,
    then drive one overload -> recovery transient per scheduler (a burst
    at 4x the feasible arrival rate, then feasible again) and record the
    peak pending backlog and that it fully drains."""
    from repro.simx.stream import run_steady_state
    from repro.workload.synth import PhasedArrivals, PoissonArrivals
    from repro.workload.synth import fixed_job_factory

    spec = STEADY_FULL if full else STEADY
    schedulers = spec["schedulers"] or list(sxe.SCHEDULERS)
    factory = fixed_job_factory(spec["tasks_per_job"], 1.0)
    demand = float(spec["tasks_per_job"])  # resource-seconds per job, exact
    kw = dict(
        window_jobs=spec["window_jobs"], window_tasks=spec["window_tasks"],
        rounds_per_refill=spec["rounds_per_refill"], seed=0,
    )
    rows = []
    for sched in schedulers:
        t0 = time.time()
        derived: dict = {}
        done = total = 0
        for load in spec["loads"]:
            rate = load * spec["num_workers"] / demand
            run = run_steady_state(
                sched,
                PoissonArrivals(rate=rate, job_factory=factory, seed=7,
                                num_jobs=spec["num_jobs"]),
                spec["num_workers"], **kw,
            )
            done += run.tasks_completed
            total += run.tasks_admitted
            tag = f"l{load:g}"
            derived[f"p99_{tag}"] = round(run.quantile(0.99), 3)
            derived[f"p999_{tag}"] = round(run.quantile(0.999), 3)
            derived[f"util_{tag}"] = round(run.mean_utilization, 4)
        # overload -> recovery transient: burst at 2x capacity, then recover
        feasible = 0.5 * spec["num_workers"] / demand
        burst_jobs = spec["num_jobs"] // 2
        run = run_steady_state(
            sched,
            PhasedArrivals(
                [(burst_jobs / (4 * feasible), feasible),
                 (burst_jobs / (4 * feasible), 4 * feasible),
                 (burst_jobs / feasible, feasible)],
                job_factory=factory, seed=7, num_jobs=burst_jobs,
            ),
            spec["num_workers"], **kw,
        )
        done += run.tasks_completed
        total += run.tasks_admitted
        wall = time.time() - t0
        assert run.tasks_completed == run.tasks_admitted, "backlog must drain"
        derived.update(
            burst_pending_peak=int(run.series["pending"].max()),
            burst_p999=round(run.quantile(0.999), 3),
            state_kb=round(run.state_bytes / 1024, 1),
            wall_s=round(wall, 2),
            done=f"{done}/{total}",
        )
        rows.append(_record(
            f"simx_steady_{sched}", wall * 1e6 / max(total, 1), **derived
        ))
    return rows


def run(
    full: bool = False,
    faults: bool = False,
    trace: bool = False,
    breakdown: bool = False,
    steady: bool = False,
    sharded: bool = False,
    trace_out: str = "simx_trace.json",
    bench_json: str | None = "BENCH_simx.json",
) -> list[str]:
    rows = []
    for workers in DC_SIZES_FULL if full else DC_SIZES:
        wl = _trace(workers)
        n_tasks = wl.num_tasks

        t0 = time.time()
        run_simulation("megha", wl, num_workers=workers, seed=0)
        ev_wall = time.time() - t0
        ev_tps = n_tasks / ev_wall
        rows.append(_record(
            f"simx_dc{workers}_events", ev_wall * 1e6 / n_tasks,
            tasks_per_sec=round(ev_tps),
            wall_s=round(ev_wall, 2),
            tasks=n_tasks,
        ))

        for dt in (0.05, 0.1):
            r = _simx_point(wl, workers, dt)
            tps = n_tasks / r["wall"]
            rows.append(_record(
                f"simx_dc{workers}_simx_dt{dt:g}", r["wall"] * 1e6 / n_tasks,
                tasks_per_sec=round(tps),
                wall_s=round(r["wall"], 2),
                compile_s=round(r["compile"], 2),
                compile_warm_s=round(r["compile_warm"], 2),
                done=f"{r['done']}/{n_tasks}",
                speedup=round(tps / ev_tps, 1),
            ))
    rows.extend(_sweep_rows(full))
    if full:  # 50k-worker compiles: minutes of wall clock, like the rest of --full
        rows.extend(_bigjob_rows())
    rows.extend(_doneprobe_row())
    rows.extend(_oracle_gap_row())
    rows.extend(_fault_smoke_row())
    rows.extend(_donation_row())
    if faults:
        rows.extend(_fault_rows(full))
    if trace:
        rows.extend(_trace_rows(trace_out))
    if breakdown:
        rows.extend(_breakdown_rows())
    if steady:
        rows.extend(_steady_rows(full))
    if sharded:
        rows.extend(_sharded_rows(full))
    if bench_json:
        write_bench_json(_BENCH_ROWS, bench_json)
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--faults", action="store_true",
                    help="add the Fig. 4 fault-severity grid rows")
    ap.add_argument("--only-faults", action="store_true",
                    help="print just the fault rows (the CI smoke entrypoint)")
    ap.add_argument("--only-bigjob", action="store_true",
                    help="print just the J-heavy queue-encoding rows")
    ap.add_argument("--only-oracle", action="store_true",
                    help="print just the oracle-gap smoke row (the CI "
                         "oracle entrypoint)")
    ap.add_argument("--trace", action="store_true",
                    help="add the telemetry trace rows and write the "
                         "Chrome-trace JSON")
    ap.add_argument("--only-trace", action="store_true",
                    help="print just the telemetry trace rows (the CI "
                         "telemetry smoke entrypoint)")
    ap.add_argument("--breakdown", action="store_true",
                    help="add the per-rule delay-decomposition rows "
                         "(oracle gap attributed to components)")
    ap.add_argument("--only-breakdown", action="store_true",
                    help="print just the delay-decomposition rows (the CI "
                         "provenance smoke entrypoint)")
    ap.add_argument("--steady", action="store_true",
                    help="add the steady-state streaming rows (tail "
                         "latency vs offered load + overload transient)")
    ap.add_argument("--only-steady", action="store_true",
                    help="print just the steady-state rows (the CI "
                         "streaming smoke entrypoint)")
    ap.add_argument("--sharded", action="store_true",
                    help="add the mesh-sharded sweep rows (device-parallel "
                         "fig2 grids + lane-batched steady state)")
    ap.add_argument("--only-sharded", action="store_true",
                    help="print just the mesh-sharded rows (the CI "
                         "sharded smoke entrypoint)")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="skip enabling the persistent JAX compilation "
                         "cache (on by default; dir from "
                         "$JAX_COMPILATION_CACHE_DIR or "
                         "<checkout>/.jax_compile_cache)")
    ap.add_argument("--trace-out", default="simx_trace.json",
                    help="Chrome-trace JSON output path (default "
                         "simx_trace.json)")
    ap.add_argument("--bench-json", default="BENCH_simx.json",
                    help="machine-readable trajectory file to merge rows "
                         "into ('none' disables)")
    args = ap.parse_args()
    bench_json = None if args.bench_json.lower() == "none" else args.bench_json
    if not args.no_compile_cache:
        enable_compile_cache()
    if args.only_faults:
        out = _fault_smoke_row() + (_fault_rows(args.full) if args.faults else [])
    elif args.only_bigjob:
        out = _bigjob_rows()
    elif args.only_oracle:
        out = _oracle_gap_row()
    elif args.only_trace:
        out = _trace_rows(args.trace_out)
    elif args.only_breakdown:
        out = _breakdown_rows()
    elif args.only_steady:
        out = _steady_rows(args.full)
    elif args.only_sharded:
        out = _sharded_rows(args.full)
    else:
        out = run(full=args.full, faults=args.faults, trace=args.trace,
                  breakdown=args.breakdown, steady=args.steady,
                  sharded=args.sharded,
                  trace_out=args.trace_out, bench_json=None)
    if bench_json:
        write_bench_json(_BENCH_ROWS, bench_json)
    for r in out:
        print(r)
