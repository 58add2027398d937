"""Shared round-stage runtime (repro.simx.runtime) + the omniscient
oracle:

* the rule registry drives the engine, the sweep drivers, and the
  ``SIMULATE_FIXED`` view — registering a rule is all the wiring there is;
* the oracle rule runs through ``sweep_grid``/``fig4_sweep`` and its
  p50/p95 job delay lower-bounds every other scheduler on the shared
  parity trace (the paper's "partial knowledge costs delay" claim,
  quantified);
* ``make_chunk_runner``, the runner the chip benchmark times, returns its
  all-done flag from inside the jitted chunk (no second device round-trip
  per chunk) and matches the plain scan bitwise, for every rule at chunk
  sizes 1, 8 and 64;
* ``engine.estimate_rounds`` is a budget every rule finishes in, on the
  synthetic trace and on Table 1's heavy-tailed mixtures;
* ``sweep.point_summary`` and ``SimxRun`` report through ONE in-jit
  job-delay reduction (``runtime.job_delays_from_state``), pinned equal;
* a hypothesis property over ALL registered rules (random trace x random
  fault schedule): per-round task accounting balances — completed +
  running + pending always covers the trace, completed/lost are monotone,
  launched + lost conserves relaunches — and the oracle stays the lower
  bound.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.simx import (
    RULES,
    SimxConfig,
    empty_schedule,
    engine,
    export_workload,
    runtime,
)
from repro.analysis.specs import check_state, dims_for
from repro.simx import oracle as simx_oracle
from repro.simx import sweep as simx_sweep
from repro.workload.synth import (
    google_like_trace,
    synthetic_trace,
    yahoo_like_trace,
)

#: The shared parity trace of tests/test_simx.py — the acceptance surface
#: for the oracle lower bound.
PARITY = dict(num_jobs=40, tasks_per_job=64, load=0.8, num_workers=256, seed=7)

#: Slack for round quantization + hop asymmetries (eagle's sticky serve
#: skips 2 hops) when comparing delay percentiles across rules.
EPS = 0.05


def _cfg(num_workers, dt=0.02):
    return SimxConfig(
        num_workers=num_workers, num_gms=4, num_lms=4, dt=dt,
        heartbeat_interval=1.0,
    )


def test_registry_covers_matrix_and_drives_the_views():
    assert engine.SCHEDULERS == ("megha", "sparrow", "eagle", "pigeon", "oracle")
    assert tuple(simx_sweep.SIMULATE_FIXED) == engine.SCHEDULERS
    assert len(simx_sweep.SIMULATE_FIXED) == 5
    assert RULES["megha"].needs_grid and not RULES["oracle"].needs_grid
    assert RULES["sparrow"].has_queues and RULES["eagle"].has_queues
    assert not RULES["megha"].has_queues
    # the view honors the Mapping protocol of the dict it replaced
    assert "nope" not in simx_sweep.SIMULATE_FIXED
    assert simx_sweep.SIMULATE_FIXED.get("nope") is None
    with pytest.raises(KeyError):
        simx_sweep.SIMULATE_FIXED["nope"]
    with pytest.raises(ValueError, match="simx backend implements"):
        runtime.get_rule("nope")
    with pytest.raises(ValueError, match="already registered"):
        runtime.register_rule(RULES["oracle"])


@pytest.fixture(scope="module")
def parity_point():
    """One (load x seed) sweep point per scheduler on the parity trace."""
    tasks = export_workload(synthetic_trace(**PARITY))
    cfg = _cfg(PARITY["num_workers"])
    rounds = engine.estimate_rounds(cfg, tasks)
    submit_g = tasks.submit[None, :]
    job_submit_g = tasks.job_submit[None, :]
    out = {}
    for name in engine.SCHEDULERS:
        out[name] = simx_sweep.sweep_grid(
            name, cfg, tasks, submit_g, job_submit_g, jnp.arange(1), rounds
        )
    return tasks, out


def test_oracle_lower_bounds_every_scheduler_on_parity_trace(parity_point):
    """Acceptance: through ``sweep_grid``, the oracle's p50/p95 delay is
    <= every other scheduler's on the shared parity trace — the gap IS
    each architecture's partial-knowledge cost."""
    tasks, grids = parity_point
    for name, grid in grids.items():
        assert int(grid["tasks_done"][0, 0]) == tasks.num_tasks, name
    o50 = float(grids["oracle"]["p50"][0, 0])
    o95 = float(grids["oracle"]["p95"][0, 0])
    for name in ("megha", "sparrow", "eagle", "pigeon"):
        assert o50 <= float(grids[name]["p50"][0, 0]) + EPS, name
        assert o95 <= float(grids[name]["p95"][0, 0]) + EPS, name
    # and the bound is non-vacuous: somebody pays a real gap
    worst = max(float(grids[n]["p95"][0, 0]) for n in ("sparrow", "eagle"))
    assert worst > o95 + EPS


def test_oracle_runs_through_fig4_sweep():
    """The oracle registers in the fault driver too: the zero-severity row
    loses nothing, crashes cost it re-runs like everyone else, and delays
    only get worse with severity."""
    r = simx_sweep.fig4_sweep(
        "oracle", fractions=(0.0, 0.25), num_seeds=2, num_workers=128,
        num_jobs=10, tasks_per_job=32, outage=2.0, dt=0.05,
    )
    assert r["p50"].shape == r["lost"].shape == (2, 2)
    assert (r["tasks_done"] == int(r["num_tasks"])).all()
    assert (r["lost"][0] == 0).all() and (r["lost"][1] > 0).all()
    assert (r["p95"][1] >= r["p95"][0] - 1e-6).all()


def test_oracle_empty_schedule_is_bitwise_noop():
    """The tentpole invariant extends to the fifth rule: an all-inf
    schedule routes through the fault-aware program yet reproduces the
    fault-free results bit for bit."""
    tasks = export_workload(
        synthetic_trace(num_jobs=8, tasks_per_job=16, load=0.8,
                        num_workers=64, seed=3)
    )
    cfg = SimxConfig(num_workers=64, dt=0.02)
    rounds = engine.estimate_rounds(cfg, tasks)
    a = simx_oracle.simulate_fixed(cfg, tasks, 0, rounds)
    b = simx_oracle.simulate_fixed(
        cfg, tasks, 0, rounds, faults=empty_schedule(64)
    )
    assert jnp.array_equal(a.task_finish, b.task_finish)
    assert jnp.array_equal(a.worker_finish, b.worker_finish)
    assert int(a.messages) == int(b.messages)
    assert int(b.lost) == 0


#: 64 rounds of a 64-worker trace: every chunk size below divides it
CHUNK_ROUNDS = 64


@functools.lru_cache(maxsize=None)
def _chunk_case(name):
    """(step, initial state, the plain 64-round scan) of one rule, shared
    by the chunk sizes."""
    tasks = export_workload(
        synthetic_trace(num_jobs=6, tasks_per_job=16, load=0.7,
                        num_workers=64, seed=2)
    )
    cfg = SimxConfig(num_workers=64, num_gms=2, num_lms=2, dt=0.05,
                     heartbeat_interval=1.0)
    rule = RULES[name]
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0))
    state0 = rule.init(cfg, tasks)
    return step, state0, runtime.scan_rounds(step, state0, CHUNK_ROUNDS)


@pytest.mark.parametrize("chunk", (1, 8, 64))
@pytest.mark.parametrize("name", ("megha", "sparrow", "eagle", "pigeon", "oracle"))
def test_chunk_runner_done_flag_matches_plain_scan(name, chunk):
    """The chip benchmark's runner, every rule: repeated
    ``make_chunk_runner`` calls up to 64 rounds reach the plain scan's
    state bitwise on every leaf; the fused all-done flag is computed inside
    the jitted chunk and agrees with the host-side probe; and
    run_to_completion still stops exactly."""
    step, state0, ref = _chunk_case(name)
    runner = engine.make_chunk_runner(step, chunk=chunk)
    s1 = state0
    for _ in range(CHUNK_ROUNDS // chunk):
        s1, done1 = runner(s1)
        assert bool(done1) == bool(jnp.all(s1.task_finish <= s1.t))
    assert int(s1.rnd) == CHUNK_ROUNDS
    la, lb = jax.tree.leaves(s1), jax.tree.leaves(ref)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    final = engine.run_to_completion(
        step, state0, chunk=chunk, max_rounds=100_000, runner=runner)
    assert bool(jnp.all(final.task_finish <= final.t))
    # the early exit fired: nowhere near the runaway budget
    assert int(final.rnd) < 100_000


#: Small traces of each generator at load 0.9 on 128 workers: the fixed
#: one-second tasks of the synthetic trace, and Table 1's heavy-tailed
#: short/long mixtures (Pareto tails up to 50x a 45 s long-task mean)
BUDGET_TRACES = {
    "synthetic": lambda: synthetic_trace(
        num_jobs=12, tasks_per_job=32, load=0.9, num_workers=128, seed=4),
    "google": lambda: google_like_trace(
        num_jobs=60, total_tasks=1200, load=0.9, num_workers=128, seed=4),
    "yahoo": lambda: yahoo_like_trace(
        num_jobs=60, total_tasks=1200, load=0.9, num_workers=128, seed=5),
}


@pytest.mark.parametrize("trace", sorted(BUDGET_TRACES))
@pytest.mark.parametrize("name", ("megha", "sparrow", "eagle", "pigeon", "oracle"))
def test_estimate_rounds_is_enough_for_every_rule(name, trace):
    """``engine.estimate_rounds`` is the round budget every driver sizes
    its run by: run to exactly that budget, every task has finished."""
    tasks = export_workload(BUDGET_TRACES[trace]())
    cfg = _cfg(128, dt=0.05)
    rule = RULES[name]
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0))
    budget = engine.estimate_rounds(cfg, tasks)
    final = engine.run_to_completion(
        step, rule.init(cfg, tasks), chunk=1024, max_rounds=budget)
    assert int(final.rnd) <= budget
    done = np.asarray(final.task_finish <= final.t)
    assert done.all(), f"{int((~done).sum())} of {tasks.num_tasks} unfinished"


def test_point_summary_and_simx_run_share_one_delay_reduction():
    """Satellite pin: sweep.point_summary (in-jit) and SimxRun (numpy)
    report THE SAME job delays — both route through
    runtime.job_delays_from_state."""
    wl = synthetic_trace(num_jobs=10, tasks_per_job=24, load=0.8,
                         num_workers=64, seed=5)
    for name in ("megha", "oracle"):
        kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0) if name == "megha" else {}
        run = engine.simulate_workload(name, wl, 64, dt=0.02, **kw)
        ps = simx_sweep.point_summary(run.state, run.tasks)
        delays = run.job_delays()
        # the vectors are the same computation (float64 view of the jit one)
        jit_delays, _ = runtime.job_delays_from_state(
            run.state.task_finish, run.state.t, run.tasks
        )
        np.testing.assert_array_equal(delays, np.asarray(jit_delays, np.float64))
        # and the percentiles agree across the two reporting paths
        np.testing.assert_allclose(
            float(ps["p50"]), np.nanpercentile(delays, 50), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            float(ps["p95"]), np.nanpercentile(delays, 95), rtol=1e-5, atol=1e-6
        )
        assert int(ps["jobs_done"]) == wl.num_jobs


# ---------------------------------------------------------------------------
# per-round conservation + the oracle bound, all five rules (the checker;
# tests/test_simx_conservation.py drives it from hypothesis, the fixed
# examples below keep it exercised when hypothesis is unavailable)
# ---------------------------------------------------------------------------

W_PROP = 32  # divides the 2 x 2 megha grid


def _prop_cfg():
    return SimxConfig(
        num_workers=W_PROP, num_gms=2, num_lms=2, dt=0.05,
        heartbeat_interval=1.0,
    )


def _prop_faults(fraction: float, fault_seed: int):
    """A random crash wave: ``fraction`` of the DC down for 1.5 s at
    t=1.0 (None when fraction == 0, exercising the fault-free build)."""
    if fraction == 0.0:
        return None
    rng = np.random.default_rng(fault_seed)
    k = max(1, int(fraction * W_PROP))
    kill = rng.permutation(W_PROP)[:k]
    down = np.full(W_PROP, np.inf, np.float32)
    up = np.full(W_PROP, np.inf, np.float32)
    down[kill], up[kill] = 1.0, 2.5
    return empty_schedule(W_PROP, 2).replace(
        worker_down=jnp.asarray(down), worker_up=jnp.asarray(up)
    )


def _per_round_counts(name, cfg, tasks, rounds, faults):
    """[rounds, 3] int32 — (completed, launched, lost) after every round,
    collected inside one jitted scan."""
    rule = RULES[name]
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0), faults=faults)

    def body(s, _):
        s2 = step(s)
        counts = jnp.stack([
            jnp.sum(s2.task_finish <= s2.t, dtype=jnp.int32),
            jnp.sum(~jnp.isinf(s2.task_finish), dtype=jnp.int32),
            s2.lost,
        ])
        return s2, counts

    final, ys = jax.lax.scan(body, rule.init(cfg, tasks), None, length=rounds)
    return final, np.asarray(ys)


def check_conservation_and_oracle_bound(
    trace_seed, num_jobs, tasks_per_job, load, fraction, fault_seed
):
    """The property, over ALL registered rules on one shared (trace, fault
    schedule): every round, completed + running + pending covers the whole
    trace with running bounded by the DC size; completed and lost are
    monotone (a crash may re-pend work but never un-complete it);
    launched + lost is monotone (relaunch accounting: a loss is always
    made up by a re-launch, never dropped); every task eventually
    completes; and the omniscient oracle's p50/p95 delay lower-bounds
    every scheduler (identical-job traces, so FIFO order is not a
    confounder)."""
    cfg = _prop_cfg()
    tasks = export_workload(
        synthetic_trace(
            num_jobs=num_jobs, tasks_per_job=tasks_per_job, load=load,
            num_workers=W_PROP, seed=trace_seed,
        )
    )
    T = tasks.num_tasks
    faults = _prop_faults(fraction, fault_seed)
    rounds = engine.estimate_rounds(cfg, tasks, slack=8.0) + int(4.0 / cfg.dt)
    summaries = {}
    spec_dims = dims_for(cfg, tasks)
    for name in engine.SCHEDULERS:
        final, ys = _per_round_counts(name, cfg, tasks, rounds, faults)
        # the final state still matches its declared shape/dtype contracts
        # (catches promotion drift the numeric assertions below can't see)
        check_state(final, dict(spec_dims), where=f"final[{name}]")
        done, launched, lost = ys[:, 0], ys[:, 1], ys[:, 2]
        # accounting balances every round
        running = launched - done
        pending = T - launched
        assert ((done >= 0) & (done <= launched) & (launched <= T)).all(), name
        assert ((running >= 0) & (running <= W_PROP)).all(), name
        assert (pending >= 0).all(), name
        assert (done + running + pending == T).all(), name
        # monotonicity: completion and loss never roll back
        assert (np.diff(done) >= 0).all(), name
        assert (np.diff(lost) >= 0).all(), name
        # relaunch conservation: every loss is re-pended, never dropped
        assert (np.diff(launched + lost) >= 0).all(), name
        # liveness: the whole trace completes inside the budget
        assert done[-1] == T, name
        if fraction == 0.0:
            assert lost[-1] == 0, name
        summaries[name] = simx_sweep.point_summary(final, tasks)
    o50 = float(summaries["oracle"]["p50"])
    o95 = float(summaries["oracle"]["p95"])
    for name in ("megha", "sparrow", "eagle", "pigeon"):
        assert o50 <= float(summaries[name]["p50"]) + 2 * cfg.dt + EPS, name
        assert o95 <= float(summaries[name]["p95"]) + 2 * cfg.dt + EPS, name


@pytest.mark.parametrize(
    "trace_seed,num_jobs,tasks_per_job,load,fraction,fault_seed",
    [
        (1, 6, 8, 0.9, 0.0, 0),    # fault-free build
        (2, 5, 10, 0.6, 0.25, 1),  # crash wave mid-run
    ],
)
def test_conservation_fixed_examples(
    trace_seed, num_jobs, tasks_per_job, load, fraction, fault_seed
):
    """Two pinned draws of the conservation property, so the checker runs
    even where hypothesis is unavailable (the full randomized sweep lives
    in tests/test_simx_conservation.py)."""
    check_conservation_and_oracle_bound(
        trace_seed, num_jobs, tasks_per_job, load, fraction, fault_seed
    )
