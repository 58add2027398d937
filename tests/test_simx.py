"""simx backend: event-backend parity, determinism, vmap, batched kernel,
and the (seed x load) sweep driver."""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import percentile
from repro.kernels.match import match_ranks_batched
from repro.kernels.ref import match_ranks_batched_ref
from repro.sim.simulator import run_simulation
from repro.simx import SimxConfig, engine, export_workload
from repro.simx import eagle as simx_eagle
from repro.simx import megha as simx_megha
from repro.simx import pigeon as simx_pigeon
from repro.simx import sparrow as simx_sparrow
from repro.simx import sweep as simx_sweep
from repro.workload.synth import synthetic_trace
from repro.workload.traces import Job, Workload

#: One small load-0.8 trace shared by the parity tests: 40 jobs x 64 tasks of
#: 1 s on a 256-worker DC — queueing-dominated delays (>> one round of dt),
#: yet fast on the event backend.
PARITY = dict(num_jobs=40, tasks_per_job=64, load=0.8, num_workers=256, seed=7)
W = PARITY["num_workers"]


@pytest.fixture(scope="module")
def parity_trace():
    return synthetic_trace(**PARITY)


def _delays(m):
    d = m.job_delays()
    return percentile(d, 50), percentile(d, 95)


def _done(m):
    return sum(1 for t in m.tasks if t.finish_time == t.finish_time)


@pytest.mark.parametrize("scheduler", ["megha", "sparrow", "eagle", "pigeon"])
def test_event_simx_parity(parity_trace, scheduler):
    kw = (
        dict(num_gms=4, num_lms=4, heartbeat_interval=1.0)
        if scheduler == "megha"
        else {}
    )
    ev = run_simulation(scheduler, parity_trace, num_workers=W, seed=0, **kw)
    sx = run_simulation(
        scheduler, parity_trace, num_workers=W, seed=0, backend="simx", dt=0.01, **kw
    )
    # identical task counts, all completed
    assert _done(ev) == _done(sx) == parity_trace.num_tasks
    p50_ev, p95_ev = _delays(ev)
    p50_sx, p95_sx = _delays(sx)
    assert p50_sx == pytest.approx(p50_ev, rel=0.15)
    assert p95_sx == pytest.approx(p95_ev, rel=0.15)
    if scheduler == "megha":
        # both backends must exhibit the eventually-consistent signature
        assert ev.inconsistencies > 0 and sx.inconsistencies > 0
        assert ev.repartitions > 0 and sx.repartitions > 0
    elif scheduler in ("sparrow", "eagle"):
        # all-short trace: no SSS rejections, so probe counts match exactly
        assert ev.probes == sx.probes > 0
    else:
        # arrival + launch messages are trace-determined for pigeon
        assert ev.messages == sx.messages > 0


@pytest.fixture(scope="module")
def mixed_trace():
    """Long + short jobs: exercises eagle's central/SSS path and pigeon's
    low-priority queue + WFQ, which the all-short parity trace cannot."""
    rng = random.Random(5)
    jobs, t = [], 0.0
    for i in range(24):
        durs = [20.0] * 8 if i % 4 == 0 else [1.0] * 32
        jobs.append(Job(job_id=i, submit_time=t, durations=durs))
        t += rng.expovariate(1.0 / 0.4)
    return Workload(name="mixed", jobs=jobs)


@pytest.mark.parametrize("scheduler", ["eagle", "pigeon"])
def test_event_simx_mixed_long_short(mixed_trace, scheduler):
    ev = run_simulation(scheduler, mixed_trace, num_workers=128, seed=0)
    sx = run_simulation(
        scheduler, mixed_trace, num_workers=128, seed=0, backend="simx", dt=0.01
    )
    assert _done(ev) == _done(sx) == mixed_trace.num_tasks
    # long tasks flow through the estimate-based path in both backends; the
    # tail (queueing-dominated) still tracks, with looser tolerance than the
    # parity pin — the long path adds approximation (see eagle/engine docs)
    _, p95_ev = _delays(ev)
    _, p95_sx = _delays(sx)
    assert p95_sx == pytest.approx(p95_ev, rel=0.3)


@pytest.fixture(scope="module")
def small():
    wl = synthetic_trace(num_jobs=10, tasks_per_job=32, load=0.8, num_workers=64, seed=3)
    tasks = export_workload(wl)
    cfg = SimxConfig(num_workers=64, num_gms=4, num_lms=4, dt=0.02, heartbeat_interval=1.0)
    return cfg, tasks, engine.estimate_rounds(cfg, tasks)


@pytest.mark.parametrize("mod", [simx_megha, simx_sparrow, simx_eagle, simx_pigeon])
def test_determinism_across_identical_seeds(small, mod):
    cfg, tasks, rounds = small
    a = mod.simulate_fixed(cfg, tasks, 5, rounds)
    b = mod.simulate_fixed(cfg, tasks, 5, rounds)
    assert jnp.array_equal(a.task_finish, b.task_finish)
    assert jnp.array_equal(a.worker_finish, b.worker_finish)
    assert int(a.messages) == int(b.messages)
    assert int(a.inconsistencies) == int(b.inconsistencies)


@pytest.mark.parametrize("mod", [simx_megha, simx_sparrow, simx_eagle, simx_pigeon])
def test_vmap_over_seeds(small, mod):
    cfg, tasks, rounds = small
    seeds = jnp.arange(3)
    run = jax.jit(
        jax.vmap(lambda s: mod.simulate_fixed(cfg, tasks, s, rounds).task_finish)
    )
    fin = run(seeds)
    assert fin.shape == (3, tasks.num_tasks)
    # every seed finishes the whole workload inside the horizon
    assert bool(jnp.all(jnp.isfinite(fin)))
    # a job can never finish before its submit + its longest task
    lower = tasks.job_submit[tasks.job] + tasks.duration
    assert bool(jnp.all(fin >= lower[None, :]))


def test_simx_pallas_match_matches_ref_backend(small):
    cfg, tasks, rounds = small
    ref_run = simx_megha.simulate_fixed(cfg, tasks, 0, rounds)
    pal_run = simx_megha.simulate_fixed(
        cfg, tasks, 0, rounds,
        match_fn=simx_megha.default_match_fn(use_pallas=True),
    )
    assert jnp.array_equal(ref_run.task_finish, pal_run.task_finish)


@pytest.mark.parametrize("g,w", [(1, 128), (4, 1000), (8, 8192), (3, 129)])
def test_match_ranks_batched_vs_ref(g, w):
    rng = np.random.default_rng(g * 1000 + w)
    avail = jnp.asarray((rng.random((g, w)) < 0.4).astype(np.int8))
    n = jnp.asarray(rng.integers(0, w + 1, g), jnp.int32)
    got = match_ranks_batched(avail, n)
    want = match_ranks_batched_ref(avail, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # each GM row assigns ranks 0..k-1 exactly once
    for i in range(g):
        r = np.asarray(got[i])
        taken = np.sort(r[r >= 0])
        np.testing.assert_array_equal(taken, np.arange(taken.size))


def test_until_caps_simulated_horizon():
    wl = synthetic_trace(
        num_jobs=8, tasks_per_job=16, task_duration=0.1, load=0.5,
        num_workers=64, seed=1,
    )
    m = run_simulation("megha", wl, num_workers=64, backend="simx", until=0.3, dt=0.05)
    fins = [t.finish_time for t in m.tasks if t.finish_time == t.finish_time]
    assert fins and max(fins) <= 0.3 + 0.05  # nothing past the horizon
    assert len(fins) < wl.num_tasks          # the cap actually truncated


def test_sparrow_simx_accepts_nondivisible_workers():
    wl = synthetic_trace(num_jobs=4, tasks_per_job=8, load=0.5, num_workers=100, seed=1)
    m = run_simulation("sparrow", wl, num_workers=100, backend="simx")
    assert _done(m) == wl.num_tasks


@pytest.fixture(scope="module")
def small_grid():
    """A tiny (2 loads x 2 seeds) grid sharing one trace structure."""
    loads = (0.5, 0.8)
    tasks, submit_g, job_submit_g = simx_sweep.make_load_grid(
        loads, num_jobs=8, tasks_per_job=16, num_workers=64, seed=11
    )
    cfg = SimxConfig(num_workers=64, num_gms=4, num_lms=4, dt=0.02,
                     heartbeat_interval=1.0)
    rounds = max(
        engine.estimate_rounds(
            cfg,
            dataclasses.replace(tasks, submit=submit_g[i], job_submit=job_submit_g[i]),
        )
        for i in range(len(loads))
    )
    seeds = jnp.arange(2)
    return cfg, tasks, submit_g, job_submit_g, seeds, rounds


@pytest.mark.parametrize("scheduler", ["megha", "sparrow", "eagle", "pigeon"])
def test_sweep_grid_matches_per_point_runs(small_grid, scheduler):
    cfg, tasks, submit_g, job_submit_g, seeds, rounds = small_grid
    grid = simx_sweep.sweep_grid(
        scheduler, cfg, tasks, submit_g, job_submit_g, seeds, rounds
    )
    assert grid["p50"].shape == (submit_g.shape[0], seeds.shape[0])
    sim = simx_sweep.SIMULATE_FIXED[scheduler]
    for li in range(submit_g.shape[0]):
        tk = dataclasses.replace(
            tasks, submit=submit_g[li], job_submit=job_submit_g[li]
        )
        for si in range(seeds.shape[0]):
            point = simx_sweep.point_summary(sim(cfg, tk, seeds[si], rounds), tk)
            # every grid point completes and equals its standalone run
            assert int(point["tasks_done"]) == tasks.num_tasks
            assert int(grid["tasks_done"][li, si]) == tasks.num_tasks
            for k in ("p50", "p95", "mean"):
                np.testing.assert_allclose(
                    np.asarray(grid[k][li, si]), np.asarray(point[k]),
                    rtol=1e-5, atol=1e-6,
                )


def test_sweep_grid_is_deterministic(small_grid):
    cfg, tasks, submit_g, job_submit_g, seeds, rounds = small_grid
    a = simx_sweep.sweep_grid("megha", cfg, tasks, submit_g, job_submit_g, seeds, rounds)
    b = simx_sweep.sweep_grid("megha", cfg, tasks, submit_g, job_submit_g, seeds, rounds)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_sparrow_probe_count_matches_event_backend():
    # d * n_tasks > W: both backends must cap probes at W per job
    wl = synthetic_trace(num_jobs=4, tasks_per_job=60, load=0.5, num_workers=64, seed=1)
    ev = run_simulation("sparrow", wl, num_workers=64, seed=0)
    sx = run_simulation("sparrow", wl, num_workers=64, backend="simx", seed=0)
    assert ev.probes == sx.probes == 4 * 64


def test_backend_arg_validation(parity_trace):
    with pytest.raises(ValueError, match="hooks"):
        run_simulation(
            "megha", parity_trace, num_workers=W, backend="simx", hooks=lambda s, l: None
        )
    with pytest.raises(ValueError, match="unknown backend"):
        run_simulation("megha", parity_trace, num_workers=W, backend="nope")
    with pytest.raises(ValueError, match="simx backend implements"):
        run_simulation("omega", parity_trace, num_workers=W, backend="simx")
