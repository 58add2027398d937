"""Round-space fault injection (repro.simx.faults, Fig. 4):

* the empty schedule is a bitwise no-op on every scheduler, and the
  Fig. 4 grid program with an empty plan reports the Fig. 2 grid's
  numbers bitwise for every rule;
* events-vs-simx parity holds under an identical mid-run fail_worker +
  fail_gm/recover_gm schedule;
* crash waves / GM windows perturb delays but never lose work;
* the unified ``run_simulation(..., faults=)`` argument works on both
  backends, and the sweep memory guard fails fast instead of OOMing.
"""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.events import EventLoop
from repro.core.megha import Megha, MeghaConfig
from repro.core.metrics import RunMetrics, percentile
from repro.sim.simulator import run_simulation
from repro.simx import (
    FaultPlan,
    FaultSchedule,
    GmOutage,
    SimxConfig,
    WorkerFailure,
    empty_schedule,
    engine,
    export_workload,
    fault_grid_schedule,
)
from repro.simx import eagle as simx_eagle
from repro.simx import megha as simx_megha
from repro.simx import pigeon as simx_pigeon
from repro.simx import sparrow as simx_sparrow
from repro.simx import sweep as simx_sweep
from repro.workload.synth import synthetic_trace
from repro.workload.traces import Job, Workload

ALL_MODS = [simx_megha, simx_sparrow, simx_eagle, simx_pigeon]


@pytest.fixture(scope="module")
def mixed():
    """Long + short jobs on a 128-worker DC (covers eagle's SSS/central
    paths and pigeon's low queue) + config + round budget."""
    rng = random.Random(5)
    jobs, t = [], 0.0
    for i in range(24):
        durs = [20.0] * 8 if i % 4 == 0 else [1.0] * 32
        jobs.append(Job(job_id=i, submit_time=t, durations=durs))
        t += rng.expovariate(1.0 / 0.4)
    tasks = export_workload(Workload(name="mixed", jobs=jobs))
    cfg = SimxConfig(
        num_workers=128, num_gms=4, num_lms=4, dt=0.02, heartbeat_interval=1.0
    )
    return cfg, tasks, engine.estimate_rounds(cfg, tasks)


@pytest.mark.parametrize("mod", ALL_MODS)
def test_empty_schedule_is_bitwise_noop(mixed, mod):
    """The tentpole invariant: a all-inf schedule routes through the
    fault-aware program yet reproduces the fault-free results bit for bit."""
    cfg, tasks, rounds = mixed
    a = mod.simulate_fixed(cfg, tasks, 5, rounds)
    b = mod.simulate_fixed(cfg, tasks, 5, rounds, faults=empty_schedule(128, 4))
    assert jnp.array_equal(a.task_finish, b.task_finish)
    assert jnp.array_equal(a.worker_finish, b.worker_finish)
    for counter in ("messages", "probes", "inconsistencies", "repartitions"):
        assert int(getattr(a, counter)) == int(getattr(b, counter))
    assert int(b.lost) == 0


@pytest.mark.parametrize("name", ("megha", "sparrow", "eagle", "pigeon", "oracle"))
def test_fault_grid_with_empty_plan_equals_the_plain_grid(name):
    """The two grid programs differ only in the fault schedule: the Fig. 4
    ``fault_sweep_grid`` run with an all-inf severity row reports, point
    for point and bit for bit, what the Fig. 2 ``sweep_grid`` reports on
    the same trace and seeds."""
    W, G = 64, 2
    cfg = SimxConfig(num_workers=W, num_gms=G, num_lms=2, dt=0.05,
                     heartbeat_interval=1.0)
    tasks = export_workload(
        synthetic_trace(num_jobs=8, tasks_per_job=16, load=0.9,
                        num_workers=W, seed=6)
    )
    rounds = engine.estimate_rounds(cfg, tasks)
    seeds = jnp.arange(2)
    plain = simx_sweep.sweep_grid(
        name, cfg, tasks, tasks.submit[None], tasks.job_submit[None], seeds,
        rounds,
    )
    faulted = simx_sweep.fault_sweep_grid(
        name, cfg, tasks,
        jax.tree.map(lambda x: x[None], empty_schedule(W, G)), seeds, rounds,
    )
    assert set(plain) == set(faulted)
    for key in plain:
        assert plain[key].shape == faulted[key].shape == (1, 2), key
        np.testing.assert_array_equal(
            np.asarray(faulted[key]), np.asarray(plain[key]), err_msg=key)
    assert (np.asarray(plain["tasks_done"]) == tasks.num_tasks).all()


@pytest.mark.parametrize("mod", ALL_MODS)
def test_crash_wave_reruns_lost_tasks(mixed, mod):
    """25% of the DC down for 3 s mid-run: in-flight tasks are lost and
    re-run (lost > 0), nothing is stranded, and delays only get worse."""
    cfg, tasks, rounds = mixed
    down = np.full(128, np.inf, np.float32)
    up = np.full(128, np.inf, np.float32)
    kill = np.random.default_rng(0).permutation(128)[:32]
    down[kill], up[kill] = 2.0, 5.0
    fs = empty_schedule(128, 4).replace(
        worker_down=jnp.asarray(down), worker_up=jnp.asarray(up)
    )
    budget = rounds + int(6.0 / cfg.dt)
    clean = mod.simulate_fixed(cfg, tasks, 5, budget)
    fault = mod.simulate_fixed(cfg, tasks, 5, budget, faults=fs)
    assert bool(jnp.all(jnp.isfinite(fault.task_finish)))
    assert int(fault.lost) > 0
    s_clean = simx_sweep.point_summary(clean, tasks)
    s_fault = simx_sweep.point_summary(fault, tasks)
    assert int(s_fault["tasks_done"]) == tasks.num_tasks
    assert float(s_fault["p95"]) >= float(s_clean["p95"]) - 1e-6


#: The shared mid-run schedule for the events-vs-simx parity pin: worker
#: crashes spread over the run (instant restart — the event backend's only
#: worker-fault mode) plus one GM down-window early in the arrival span.
PARITY_PLAN = FaultPlan(
    worker_failures=(
        WorkerFailure(3, 4.0),
        WorkerFailure(50, 5.5),
        WorkerFailure(97, 7.0),
        WorkerFailure(200, 8.5),
    ),
    gm_outages=(GmOutage(1, 0.2, 0.8),),
)


def test_event_simx_parity_under_faults():
    """Aggregate p50/p95 parity on the parity trace under an identical
    fail_worker + fail_gm/recover_gm schedule (the §3.5 events semantics
    resubmit orphaned jobs wholesale; simx adopts their queues — the
    engine docstring's fault contract covers the residual drift)."""
    wl = synthetic_trace(
        num_jobs=40, tasks_per_job=64, load=0.8, num_workers=256, seed=7
    )
    kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0)
    ev = run_simulation(
        "megha", wl, num_workers=256, seed=0, faults=PARITY_PLAN, **kw
    )
    sx = run_simulation(
        "megha", wl, num_workers=256, seed=0, backend="simx", dt=0.01,
        faults=PARITY_PLAN, **kw
    )
    d_ev, d_sx = ev.job_delays(), sx.job_delays()
    # every job finishes on both backends despite the faults
    assert len(d_sx) == wl.num_jobs
    assert len(d_ev) >= wl.num_jobs  # resubmitted jobs may duplicate records
    assert percentile(d_sx, 50) == pytest.approx(percentile(d_ev, 50), rel=0.15)
    assert percentile(d_sx, 95) == pytest.approx(percentile(d_ev, 95), rel=0.15)
    # both backends paid for the faults in the §3.4 accounting
    assert ev.inconsistencies > 0 and sx.inconsistencies > 0


def test_gm_down_window_is_absorbed_and_recovers():
    """One GM down mid-run: live GMs adopt its queue (jobs keep finishing),
    and a recovery view reset costs one snapshot per LM in messages."""
    wl = synthetic_trace(
        num_jobs=24, tasks_per_job=32, load=0.7, num_workers=256, seed=3
    )
    kw = dict(
        num_gms=4, num_lms=4, heartbeat_interval=1.0, backend="simx", dt=0.02
    )
    clean = run_simulation("megha", wl, num_workers=256, **kw)
    plan = FaultPlan(gm_outages=(GmOutage(2, 0.3, 1.5),))
    fault = run_simulation("megha", wl, num_workers=256, faults=plan, **kw)
    assert len(fault.job_delays()) == wl.num_jobs
    assert percentile(fault.job_delays(), 95) >= percentile(clean.job_delays(), 95) - 1e-6

    # the whole scheduling tier down: arrivals freeze, then drain on recovery
    all_down = FaultPlan(
        gm_outages=tuple(GmOutage(g, 0.5, 1.5) for g in range(4))
    )
    frozen = run_simulation("megha", wl, num_workers=256, faults=all_down, **kw)
    assert len(frozen.job_delays()) == wl.num_jobs


def test_fig4_sweep_compiles_severity_grid():
    """The Fig. 4 driver: one vmapped program over (fraction x seed); the
    zero-severity row must lose nothing and severity only adds delay."""
    r = simx_sweep.fig4_sweep(
        "megha",
        fractions=(0.0, 0.25),
        num_seeds=2,
        num_workers=256,
        num_jobs=12,
        tasks_per_job=64,
        outage=2.0,
        gm_outages=1,
        dt=0.05,
        num_gms=4,
        num_lms=4,
        heartbeat_interval=1.0,
    )
    assert r["p50"].shape == r["lost"].shape == (2, 2)
    assert (r["tasks_done"] == int(r["num_tasks"])).all()
    assert (r["lost"][0] == 0).all() and (r["lost"][1] > 0).all()
    assert (r["p95"][1] >= r["p95"][0] - 1e-6).all()


def test_fig4_zero_severity_matches_unfaulted_run():
    """Severity 0 inside the vmapped grid == a standalone fault-free run."""
    cfg = SimxConfig(num_workers=128, dt=0.05)
    tasks = export_workload(
        synthetic_trace(num_jobs=8, tasks_per_job=32, load=0.8,
                        num_workers=128, seed=11)
    )
    rounds = engine.estimate_rounds(cfg, tasks)
    schedules = fault_grid_schedule(
        128, cfg.num_gms, (0.0, 0.2), fail_time=1.0, outage=1.0, dt=0.05
    )
    grid = simx_sweep.fault_sweep_grid(
        "sparrow", cfg, tasks, schedules, jnp.arange(1), rounds
    )
    solo = simx_sweep.point_summary(
        simx_sparrow.simulate_fixed(cfg, tasks, 0, rounds), tasks
    )
    for k in ("p50", "p95", "mean"):
        np.testing.assert_allclose(
            np.asarray(grid[k][0, 0]), np.asarray(solo[k]), rtol=1e-6
        )


def test_unified_faults_arg_on_events_backend():
    """run_simulation(faults=FaultPlan) drives the imperative hooks."""
    wl = synthetic_trace(
        num_jobs=8, tasks_per_job=16, load=0.6, num_workers=64, seed=2
    )
    plan = FaultPlan(worker_failures=(WorkerFailure(0, 0.5),))
    m = run_simulation(
        "megha", wl, num_workers=64, num_gms=2, num_lms=2, faults=plan
    )
    assert len(m.job_delays()) == wl.num_jobs

    # baselines have no event-backend fault hooks -> actionable error
    with pytest.raises(ValueError, match="backend='simx'"):
        run_simulation("sparrow", wl, num_workers=64, faults=plan)
    # worker down-windows only exist in round space
    windowed = FaultPlan(worker_failures=(WorkerFailure(0, 0.5, 2.0),))
    with pytest.raises(ValueError, match="down-window"):
        run_simulation(
            "megha", wl, num_workers=64, num_gms=2, num_lms=2, faults=windowed
        )
    # dense schedules are simx-only; events takes the neutral plan
    with pytest.raises(ValueError, match="FaultPlan"):
        run_simulation("megha", wl, num_workers=64, faults=empty_schedule(64))


def test_simx_faults_shape_validation():
    wl = synthetic_trace(
        num_jobs=4, tasks_per_job=8, load=0.5, num_workers=64, seed=1
    )
    with pytest.raises(ValueError, match="covers"):
        run_simulation(
            "sparrow", wl, num_workers=64, backend="simx",
            faults=empty_schedule(32),
        )


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="outside"):
        FaultPlan(worker_failures=(WorkerFailure(99, 1.0),)).to_schedule(8, 2, 0.05)
    with pytest.raises(ValueError, match="before"):
        FaultPlan(worker_failures=(WorkerFailure(0, 1.0, 0.5),)).to_schedule(8, 2, 0.05)
    with pytest.raises(ValueError, match="before"):
        FaultPlan(gm_outages=(GmOutage(0, 1.0, 0.5),)).to_schedule(8, 2, 0.05)
    with pytest.raises(ValueError, match="fractions"):
        fault_grid_schedule(8, 2, (1.0,), fail_time=1.0, outage=1.0)
    # one crash window per entity: duplicates would silently diverge from
    # the event backend's replay of every entry
    dup_w = FaultPlan(
        worker_failures=(WorkerFailure(5, 1.0), WorkerFailure(5, 3.0))
    )
    with pytest.raises(ValueError, match="duplicate worker"):
        dup_w.to_schedule(8, 2, 0.05)
    dup_g = FaultPlan(
        gm_outages=(GmOutage(1, 1.0, 2.0), GmOutage(1, 3.0, 4.0))
    )
    with pytest.raises(ValueError, match="duplicate GM"):
        dup_g.to_schedule(8, 2, 0.05)
    # the events installer validates ranges and duplicates the same way
    wl = synthetic_trace(
        num_jobs=2, tasks_per_job=4, load=0.5, num_workers=32, seed=0
    )
    with pytest.raises(ValueError, match="duplicate worker"):
        run_simulation(
            "megha", wl, num_workers=32, num_gms=2, num_lms=2, faults=dup_w
        )
    oob = FaultPlan(worker_failures=(WorkerFailure(9999, 1.0),))
    with pytest.raises(ValueError, match="outside"):
        run_simulation(
            "megha", wl, num_workers=32, num_gms=2, num_lms=2, faults=oob
        )


def test_submit_reroutes_past_failed_gms():
    """Satellite: arrivals round-robin past down GMs instead of crashing;
    only a fully dead scheduling tier errors out."""
    loop = EventLoop()
    cfg = MeghaConfig(num_workers=32, num_gms=4, num_lms=2)
    sched = Megha(loop, RunMetrics("megha", "reroute"), cfg)
    sched.fail_gm(0)
    sched.fail_gm(1)
    # 8 submissions all land on the two live GMs, no assertion/crash
    for i in range(8):
        sched.submit(Job(i, 0.0, [0.5] * 4))
    loop.run()
    assert all(j.finish_time == j.finish_time for j in sched.metrics.jobs)
    sched2 = Megha(EventLoop(), RunMetrics("megha", "dead"), cfg)
    for g in range(4):
        sched2.fail_gm(g)
    with pytest.raises(RuntimeError, match="no live GM"):
        sched2.submit(Job(99, 0.0, [1.0]))


def test_recovered_gm_drops_predecessor_lm_responses():
    """A fresh GM recovered into a failed GM's slot may receive LM
    responses to its predecessor's proposals: invalid mappings for jobs it
    never saw are dropped, not KeyErrors (the orphaned job is resubmitted
    elsewhere per §3.5)."""
    from repro.core.megha import _Mapping

    loop = EventLoop()
    cfg = MeghaConfig(num_workers=32, num_gms=4, num_lms=2)
    sched = Megha(loop, RunMetrics("megha", "stale-response"), cfg)
    sched.fail_gm(1)
    gm = sched.recover_gm(1)
    stale = _Mapping(job_id=123, task_index=0, worker=0, duration=1.0,
                     borrowed=False)
    gm.on_lm_response(0, [], [stale], snapshot=[True] * cfg.workers_per_lm)
    assert sched.metrics.inconsistencies == 1  # accounted, not crashed


def test_probe_memory_guard_fails_fast():
    """Satellite: the sweep memory model is the O(W * R) reservation-queue
    footprint — MBs where the dense [J, W] encoding needed GiBs — and the
    guard survives only as a safety valve."""
    est = simx_sweep.probe_memory_bytes("sparrow", 480, 50_000, 6)
    dense = simx_sweep.DENSE_JW_BYTES_PER_ELEM["sparrow"] * 480 * 50_000 * 6
    assert 0 < est < 2**28 < dense  # the ROADMAP's old ~1.7 GiB, now < 256 MB
    assert simx_sweep.probe_memory_bytes("megha", 480, 50_000, 6) == 0
    # the paper-scale Fig. 2 grid AND a J-heavy (2000-job) point both clear
    # the default 16 GiB ceiling now: the carried state no longer scales
    # with the job count (acceptance criterion for the [W, R] encoding)
    for j in (480, 2000, 100_000):
        simx_sweep.check_probe_memory("sparrow", j, 50_000, 6, 16 * 2**30)
    with pytest.raises(RuntimeError, match="reservation-queue"):
        simx_sweep.check_probe_memory("eagle", 480, 50_000, 6, 2**20)
    # the drivers still fail BEFORE building traces or compiling
    with pytest.raises(RuntimeError, match="mem_limit_gb"):
        simx_sweep.fig2_sweep(
            "sparrow", loads=(0.5,), num_seeds=1, num_workers=50_000,
            num_jobs=480, tasks_per_job=1000, mem_limit_gb=0.001,
        )
    with pytest.raises(RuntimeError, match="mem_limit_gb"):
        simx_sweep.fig4_sweep(
            "eagle", fractions=(0.0, 0.1), num_seeds=2, num_workers=50_000,
            num_jobs=480, tasks_per_job=1000, mem_limit_gb=0.001,
        )


def test_run_simulation_simx_all_schedulers_with_faults():
    """Acceptance: the front door runs all four schedulers with a nonzero
    schedule through the simx backend."""
    wl = synthetic_trace(
        num_jobs=6, tasks_per_job=16, load=0.6, num_workers=64, seed=4
    )
    plan = FaultPlan(
        worker_failures=tuple(WorkerFailure(w, 0.8, 1.6) for w in (1, 17, 33))
    )
    for sched in ("megha", "sparrow", "eagle", "pigeon"):
        kw = dict(num_gms=2, num_lms=2) if sched == "megha" else {}
        m = run_simulation(
            sched, wl, num_workers=64, backend="simx", dt=0.02, faults=plan, **kw
        )
        assert len(m.job_delays()) == wl.num_jobs, sched
