"""The in-scan telemetry stage (repro.simx.telemetry + runtime stage 4):

* the tentpole invariant: telemetry OFF builds exactly the pre-telemetry
  program — final state bitwise-identical for ALL five rules, on both the
  stride-divisible and trailing-partial-window scan paths;
* decimated series shapes and units: one sample per ``stride`` rounds,
  ``t`` on the round clock, gauges in range, counter windows summing to
  the final state's cumulative totals, the delay histogram covering
  exactly the finished jobs;
* gauge conservation: pending + running + completed == arrived at every
  sample;
* backend parity: the events backend and simx count THE SAME sparrow
  probes (min(d * n, W) per job, closed form), and
  ``RunMetrics.overhead_summary()`` mirrors ``sweep.point_summary``'s
  overhead columns;
* the engine surface: ``simulate_workload(..., telemetry=...)`` attaches
  a ``Timeline`` without perturbing the run, and ``to_chrome_trace()``
  round-trips through JSON as pure counter/metadata events;
* the ``full_width_rounds`` counter: 1 in the rounds whose queue passes
  ran at the cap, because the longest reservation queue outgrew the
  narrow width.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.simx import (
    RULES,
    SimxConfig,
    TelemetryConfig,
    engine,
    export_workload,
    runtime,
)
from repro.simx import sparrow as simx_sparrow
from repro.simx import sweep as simx_sweep
from repro.sim.simulator import run_simulation
from repro.workload.synth import synthetic_trace

#: The shared parity trace of tests/test_simx.py — the acceptance surface
#: for the cross-backend probe-counter pin.
PARITY = dict(num_jobs=40, tasks_per_job=64, load=0.8, num_workers=256, seed=7)

#: Telemetry trace: small enough to compile 5 rules x 3 programs, busy
#: enough that every counter moves.  128 divides the 4 x 4 megha grid.
TRACE = dict(num_jobs=16, tasks_per_job=64, load=0.8, num_workers=128, seed=13)
ROUNDS = 200


def _cfg(num_workers, dt=0.05):
    return SimxConfig(
        num_workers=num_workers, num_gms=4, num_lms=4, dt=dt,
        heartbeat_interval=1.0,
    )


@pytest.fixture(scope="module")
def trace():
    return _cfg(TRACE["num_workers"]), export_workload(synthetic_trace(**TRACE))


@pytest.mark.parametrize("name", ("megha", "sparrow", "eagle", "pigeon", "oracle"))
def test_disabled_telemetry_is_bitwise_noop(name, trace):
    """ISSUE acceptance: running with telemetry and throwing the Timeline
    away reproduces the telemetry-free final state bit for bit — the
    counter plumbing is only BUILT under the flag, never traced-and-DCEd.
    stride=4 divides ROUNDS (pure decimated path); stride=7 leaves a
    trailing partial window (the ``advance_plain`` path)."""
    cfg, tasks = trace
    plain = runtime.simulate_fixed(name, cfg, tasks, 0, ROUNDS)
    strides = (4, 7) if name in ("oracle", "megha") else (4,)
    for stride in strides:
        tele, tl = runtime.simulate_fixed(
            name, cfg, tasks, 0, ROUNDS, telemetry=TelemetryConfig(stride=stride)
        )
        la, lb = jax.tree.leaves(plain), jax.tree.leaves(tele)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert tl.num_samples == ROUNDS // stride


def test_timeline_series_shapes_and_units(trace):
    cfg, tasks = trace
    tel = TelemetryConfig(stride=4)
    state, tl = runtime.simulate_fixed(
        "sparrow", cfg, tasks, 0, ROUNDS, telemetry=tel
    )
    K = ROUNDS // tel.stride
    assert tl.num_samples == K and tl.t.shape == (K,)
    # t[k] is the simulated time at the END of window k
    np.testing.assert_allclose(
        np.asarray(tl.t), cfg.dt * tel.stride * np.arange(1, K + 1), rtol=1e-5
    )
    for key, v in tl.series.items():
        assert v.shape == (K,), key
    util = np.asarray(tl.series["utilization"])
    assert ((util >= 0.0) & (util <= 1.0)).all() and util.max() > 0.0
    assert (np.diff(np.asarray(tl.series["completed"])) >= 0).all()
    # counter windows sum to the final state's cumulative totals (rem == 0)
    assert int(np.sum(tl.series["messages"])) == int(state.messages)
    assert int(np.sum(tl.series["probes"])) == int(state.probes)
    assert int(np.sum(tl.series["launches"])) == int(
        jnp.sum(~jnp.isinf(state.task_finish))
    )
    # reservation-queue rules export their queue counters as series
    assert {"res_overflow", "probe_lag"} <= tl.series.keys()
    # delay histogram: exactly one entry per finished job
    delays, _ = runtime.job_delays_from_state(state.task_finish, state.t, tasks)
    assert int(np.sum(tl.delay_hist)) == int(
        np.isfinite(np.asarray(delays)).sum()
    )
    assert tl.bin_edges.shape == (tel.delay_bins + 1,)
    assert tl.bin_edges[-1] == tel.delay_max


def test_rule_extra_counters_become_series(trace):
    """Each rule's dispatch-supplied extras surface as Timeline series."""
    cfg, tasks = trace
    extras = {
        "megha": "view_repairs",
        "eagle": "sss_rejections",
        "pigeon": "reserve_hits",
    }
    for name, key in extras.items():
        _, tl = runtime.simulate_fixed(
            name, cfg, tasks, 0, 64, telemetry=TelemetryConfig(stride=8)
        )
        assert key in tl.series, name
        assert "launches" in tl.series, name


def test_gauges_conserve_task_accounting(trace):
    """pending + running + completed == tasks arrived, at every sample."""
    cfg, tasks = trace
    _, tl = runtime.simulate_fixed(
        "megha", cfg, tasks, 0, ROUNDS, telemetry=TelemetryConfig(stride=4)
    )
    t = np.asarray(tl.t, np.float64)
    arrived = (np.asarray(tasks.submit)[None, :] <= t[:, None]).sum(axis=1)
    total = (
        np.asarray(tl.series["pending"])
        + np.asarray(tl.series["running"])
        + np.asarray(tl.series["completed"])
    )
    np.testing.assert_array_equal(total, arrived)
    assert (np.asarray(tl.series["live_workers"]) == cfg.num_workers).all()
    assert (np.asarray(tl.series["queue_depth"]) <= tasks.num_jobs).all()


def test_probe_counter_parity_events_vs_simx():
    """Both backends count the same sparrow probe traffic — the closed
    form Σ_j min(d · n_j, W) — and report it through the same
    overhead_summary shape."""
    wl = synthetic_trace(**PARITY)
    tasks = export_workload(wl)
    counts = np.bincount(np.asarray(tasks.job), minlength=tasks.num_jobs)
    W = PARITY["num_workers"]
    expected = int(sum(min(2 * int(n), W) for n in counts))

    ev = run_simulation("sparrow", wl, num_workers=W)
    sx = engine.simulate_workload("sparrow", wl, W)
    assert ev.probes == expected
    assert int(sx.state.probes) == expected

    evo = ev.overhead_summary()
    sxo = sx.to_run_metrics(include_tasks=False).overhead_summary()
    assert set(evo) == set(sxo) == {
        "messages", "probes", "inconsistencies", "inconsistency_rate",
    }
    assert evo["probes"] == sxo["probes"] == expected
    assert evo["inconsistencies"] == sxo["inconsistencies"] == 0
    # the sweep reductions expose the same columns from the raw state
    ps = simx_sweep.point_summary(sx.state, sx.tasks)
    assert int(ps["probes"]) == expected
    assert float(ps["inconsistency_rate"]) == sxo["inconsistency_rate"]


def test_point_summary_overhead_columns_and_queue_gating(trace):
    cfg, tasks = trace
    s_megha = runtime.simulate_fixed("megha", cfg, tasks, 0, ROUNDS)
    s_sparrow = runtime.simulate_fixed("sparrow", cfg, tasks, 0, ROUNDS)
    pm = simx_sweep.point_summary(s_megha, tasks)
    psp = simx_sweep.point_summary(s_sparrow, tasks)
    assert 0.0 < float(pm["mean_util"]) <= 1.0
    assert 0.0 < float(psp["mean_util"]) <= 1.0
    # megha carries no reservation queues: the columns are literal zeros,
    # not getattr fallbacks (explicit has_queues gating)
    assert not RULES["megha"].has_queues
    assert int(pm["res_overflow"]) == 0 and int(pm["probe_lag"]) == 0
    np.testing.assert_allclose(
        float(pm["inconsistency_rate"]),
        int(pm["inconsistencies"]) / tasks.num_tasks,
        rtol=1e-6,
    )
    # the isinstance default agrees with the registry flag on both sides
    for st, rule in ((s_megha, RULES["megha"]), (s_sparrow, RULES["sparrow"])):
        a = simx_sweep.point_summary(st, tasks)
        b = simx_sweep.point_summary(st, tasks, has_queues=rule.has_queues)
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_engine_timeline_and_chrome_trace():
    """simulate_workload(..., telemetry=) attaches a Timeline without
    perturbing the run; to_chrome_trace round-trips through JSON as
    counter ("C") + metadata ("M") events on one pid."""
    wl = synthetic_trace(num_jobs=10, tasks_per_job=24, load=0.8,
                         num_workers=64, seed=5)
    kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0, dt=0.05)
    base = engine.simulate_workload("megha", wl, 64, **kw)
    run = engine.simulate_workload(
        "megha", wl, 64, telemetry=TelemetryConfig(stride=4), **kw
    )
    assert base.timeline is None and run.timeline is not None
    assert jnp.array_equal(base.state.task_finish, run.state.task_finish)
    assert jnp.array_equal(base.state.worker_finish, run.state.worker_finish)
    assert int(base.state.messages) == int(run.state.messages)
    # telemetry=True sugars to the default TelemetryConfig
    sugar = engine.simulate_workload("megha", wl, 64, telemetry=True, **kw)
    assert sugar.timeline is not None
    assert sugar.timeline.stride == TelemetryConfig().stride

    tl = run.timeline
    tr = json.loads(json.dumps(tl.to_chrome_trace(pid=3, process_name="simx:megha")))
    evs = tr["traceEvents"]
    assert evs and tr["displayTimeUnit"] == "ms"
    assert evs[0] == {
        "name": "process_name", "ph": "M", "pid": 3, "tid": 0,
        "args": {"name": "simx:megha"},
    }
    assert all(e["ph"] in ("C", "M") for e in evs)
    assert all(e["pid"] == 3 for e in evs)
    comp = [e["args"]["completed"] for e in evs if e["name"] == "completed"]
    assert len(comp) == tl.num_samples
    assert comp == sorted(comp)
    ts = [e["ts"] for e in evs if e["name"] == "completed"]
    np.testing.assert_allclose(ts, np.asarray(tl.t, np.float64) * 1e6, rtol=1e-6)


def test_full_width_rounds_counts_rounds_at_the_cap():
    """Sparrow's ``full_width_rounds`` is 1 in exactly the rounds whose
    longest queue after insertion, counted in numpy from the round's
    state, outgrows the narrow width, so that late binding's queue passes
    ran at the cap R; a burst of arrivals drives it across both widths.
    A decimated window sums it like every other counter."""
    wl = synthetic_trace(num_jobs=80, tasks_per_job=8, load=1.0,
                         num_workers=16, seed=4)
    tasks = export_workload(wl)
    tasks = dataclasses.replace(                        # arrivals in a burst
        tasks, submit=tasks.submit * 0.05, job_submit=tasks.job_submit * 0.05
    )
    cfg = SimxConfig(num_workers=16, dt=0.02, reserve_cap=64)
    narrow = simx_sparrow.queue_widths(64)[-2]
    rounds = 240
    step = jax.jit(runtime.point_step("sparrow", cfg, tasks, 0, telemetry=True))
    state = runtime.init_carry("sparrow", cfg, tasks)
    got, want = [], []
    for _ in range(rounds):
        state, counters = step(state)
        longest = int((np.asarray(state.resq) < tasks.num_jobs).sum(axis=1).max())
        want.append(int(longest > narrow))
        got.append(int(counters["full_width_rounds"]))
    assert got == want
    assert 0 < sum(want) < rounds
    _, tl = runtime.simulate_fixed(
        "sparrow", cfg, tasks, 0, rounds, telemetry=TelemetryConfig(stride=4)
    )
    np.testing.assert_array_equal(
        np.asarray(tl.series["full_width_rounds"]),
        np.asarray(got).reshape(-1, 4).sum(axis=1),
    )
