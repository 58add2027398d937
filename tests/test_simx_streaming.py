"""Streaming steady-state engine battery (``repro.simx.stream``).

Parity-first: the ring-buffer window is an *implementation* of the same
round dynamics the fixed-trace path runs, so the pin is behavioral —
streaming a finite trace through ``run_steady_state`` must reproduce the
fixed path's final counters for every registered rule, exactly for the
deterministic rules (megha / pigeon / oracle share the fixed path's
per-global-job-id assignments) and within tolerance for the probe rules
(sparrow / eagle host-sample probe targets per global job id instead of
the fixed path's in-jit draw).  On top of the pin: window-recycling
conservation at every refill boundary, bitwise determinism, the
O(W + window) carried-state-bytes assertion, the P² sketch error
contract, and the jitted remainder runner (``engine._run_tail``)
regression.
"""

import functools

import numpy as np
import pytest

from conftest import require_or_skip_hypothesis

import jax.numpy as jnp

from repro.simx import engine
from repro.simx import runtime as rt
from repro.simx import telemetry as tlm
from repro.simx.state import SimxConfig
from repro.simx.stream import run_steady_state
from repro.workload.synth import (
    PoissonArrivals,
    ReplayArrivals,
    bimodal_job_factory,
    synthetic_trace,
)

try:
    from hypothesis import example, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # locally optional; CI sets REQUIRE_HYPOTHESIS
    HAVE_HYPOTHESIS = False

RULES = ("megha", "sparrow", "eagle", "pigeon", "oracle")
#: rules whose streamed path replays the fixed path's exact decisions
EXACT = ("megha", "pigeon", "oracle")

W, GMS, LMS = 128, 4, 4
_WL = synthetic_trace(
    num_jobs=60, tasks_per_job=8, task_duration=1.0, load=0.7,
    num_workers=W, seed=3,
)


@functools.lru_cache(maxsize=None)
def _fixed(rule):
    return engine.simulate_workload(rule, _WL, W, num_gms=GMS, num_lms=LMS, seed=0)


@functools.lru_cache(maxsize=None)
def _streamed(rule):
    """Full-capacity window: every refill admits everything — the stream
    IS the fixed trace, so this is the parity configuration."""
    return run_steady_state(
        rule, ReplayArrivals(_WL), W,
        window_jobs=_WL.num_jobs, window_tasks=_WL.num_tasks,
        rounds_per_refill=64, num_gms=GMS, num_lms=LMS, seed=0,
    )


@functools.lru_cache(maxsize=None)
def _small(rule):
    """Window far smaller than the trace — jobs carry across many refills
    and admission is capacity-throttled (the recycling stress shape)."""
    return run_steady_state(
        rule, ReplayArrivals(_WL), W,
        window_jobs=8, window_tasks=80,
        rounds_per_refill=16, num_gms=GMS, num_lms=LMS, seed=0,
    )


# ---------------------------------------------------------------------------
# parity pin: streamed replay vs the fixed-trace path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", RULES)
def test_stream_parity_counters(rule):
    fixed, run = _fixed(rule), _streamed(rule)
    assert run.tasks_admitted == _WL.num_tasks
    assert run.tasks_completed == fixed.tasks_completed == _WL.num_tasks
    assert run.jobs_completed == run.jobs_admitted == _WL.num_jobs
    assert run.lost == fixed.lost_tasks == 0


@pytest.mark.parametrize("rule", RULES)
def test_stream_parity_delays(rule):
    fd = _fixed(rule).job_delays()
    fd = fd[np.isfinite(fd)]
    sd = _streamed(rule).delays
    assert sd.shape == fd.shape
    f50, f95 = np.percentile(fd, 50), np.percentile(fd, 95)
    s50, s95 = np.percentile(sd, 50), np.percentile(sd, 95)
    if rule in EXACT:
        # deterministic rules: the streamed window replays the exact same
        # decisions, so delays match to float32 noise
        np.testing.assert_allclose(np.sort(sd), np.sort(fd), atol=1e-5)
    else:
        # probe rules differ only in where probe targets are drawn
        # (host per-global-job-id vs in-jit) — same distribution, so the
        # tail percentiles agree within sampling tolerance
        assert s50 <= 2.0 * f50 + 0.05 and f50 <= 2.0 * s50 + 0.05
        assert abs(s95 - f95) <= 0.35 * max(f95, s95) + 0.05


@pytest.mark.parametrize("rule", RULES)
def test_stream_sketch_tracks_exact_delays(rule):
    """The in-jit sketch absorbed every retired job exactly once — with
    only 60 jobs its p50 is the nearest-rank estimate of the exact host
    delays ``collect_delays`` kept."""
    run = _streamed(rule)
    assert run.quantile_targets == tlm.DEFAULT_QUANTILES
    exact = np.quantile(run.delays, 0.5)
    spread = float(run.delays.max() - run.delays.min())
    assert abs(run.quantile(0.5) - exact) <= 0.25 * spread + 1e-6


# ---------------------------------------------------------------------------
# window recycling: conservation, completion, determinism, state bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", RULES)
def test_window_recycling_conservation(rule):
    """At every refill boundary the admitted stream partitions exactly:
    arrived == completed + running + pending + unarrived + lost."""
    run = _small(rule)
    assert len(run.refills) >= 8  # the window actually recycled
    for s in run.refills:
        assert s["admitted"] == (
            s["completed"] + s["running"] + s["pending"]
            + s["unarrived"] + s["lost"]
        ), s
        assert s["window_jobs"] <= 8


@pytest.mark.parametrize("rule", RULES)
def test_small_window_drains_the_stream(rule):
    run = _small(rule)
    assert run.tasks_completed == _WL.num_tasks
    assert run.jobs_completed == _WL.num_jobs
    assert run.lost == 0


def test_stream_determinism():
    """Same seed => bitwise-identical streamed chunks: delays, counters,
    and the whole gauge series."""
    arr = lambda: PoissonArrivals(  # noqa: E731
        rate=4.0, job_factory=bimodal_job_factory(), seed=11, num_jobs=24
    )
    kw = dict(window_jobs=8, window_tasks=128, rounds_per_refill=16,
              num_gms=GMS, num_lms=LMS, seed=0)
    a = run_steady_state("sparrow", arr(), W, **kw)
    b = run_steady_state("sparrow", arr(), W, **kw)
    assert np.array_equal(a.delays, b.delays)
    assert (a.tasks_completed, a.probes, a.messages) == (
        b.tasks_completed, b.probes, b.messages)
    for k in a.series:
        # the sketch reads NaN until it has 5 samples, so compare NaN-aware
        assert np.array_equal(a.series[k], b.series[k], equal_nan=True), k
    assert a.refills == b.refills


def test_state_bytes_independent_of_span():
    """The O(W + window) claim, measured: double the simulated trace and
    the carried device footprint (state + window arrays + layout +
    sketch) does not change by a byte."""
    long_wl = synthetic_trace(
        num_jobs=120, tasks_per_job=8, task_duration=1.0, load=0.7,
        num_workers=W, seed=3,
    )
    kw = dict(window_jobs=8, window_tasks=80, rounds_per_refill=16,
              num_gms=GMS, num_lms=LMS, seed=0)
    short = _small("oracle")
    long_run = run_steady_state("oracle", ReplayArrivals(long_wl), W, **kw)
    assert long_run.tasks_completed == long_wl.num_tasks
    assert long_run.state_bytes == short.state_bytes
    # and it is actually small: far under the 2x trace's own task arrays
    assert short.state_bytes < 64 * 1024


# ---------------------------------------------------------------------------
# P^2 sketch error contract
# ---------------------------------------------------------------------------


def _sketch_rank_error(samples: np.ndarray, q: float) -> float:
    sk = tlm.sketch_init((q,))
    vals = jnp.asarray(samples, jnp.float32)
    sk = tlm.sketch_absorb(sk, vals, jnp.ones(vals.shape, bool))
    est = float(np.asarray(tlm.sketch_quantiles(sk))[0])
    return abs(float(np.mean(samples <= est)) - q)


def test_sketch_error_contract_shuffled():
    """The documented contract: rank error <= 0.05 on exchangeable
    (shuffled) streams of >= 1000 samples — a bimodal mixture, the shape
    scheduler delay distributions actually take."""
    rng = np.random.default_rng(7)
    samples = np.concatenate([
        rng.lognormal(0.0, 0.5, 1500), 5.0 + rng.lognormal(0.5, 0.3, 500),
    ])
    rng.shuffle(samples)
    for q in tlm.DEFAULT_QUANTILES:
        assert _sketch_rank_error(samples, q) <= 0.05, q


if HAVE_HYPOTHESIS:

    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(
        seed=st.integers(0, 2**31 - 1),
        n=st.integers(1000, 3000),
        sigma=st.floats(0.1, 1.0),
        split=st.floats(0.1, 0.9),
    )
    # 60% of the mass in a narrow lower mode: the median's neighbourhood
    # (ranks 0.25 to 0.75) spans the gap, and P2 puts the median in it,
    # rank error 0.102; the tail targets stay inside the contract
    @example(seed=57160454, n=2463, sigma=0.11211557029415574,
             split=0.6022291879861058)
    def test_sketch_vs_exact_quantiles_property(seed, n, sigma, split):
        """Property form of the error contract: on any shuffled two-mode
        lognormal mixture the estimate stays within the documented
        +/-0.05 rank error at every default target whose P2
        neighbourhood, ranks p/2 to (1 + p)/2, does not span the gap
        between the modes (the lower mode holds ``split`` of the mass).
        The tail targets hold it whatever the split."""
        rng = np.random.default_rng(seed)
        k = int(n * split)
        samples = np.concatenate([
            rng.lognormal(0.0, sigma, k),
            4.0 + rng.lognormal(0.0, sigma, n - k),
        ])
        rng.shuffle(samples)
        for q in tlm.DEFAULT_QUANTILES:
            gap_in_reach = q / 2 < k / n < (1 + q) / 2
            if q >= 0.95 or not gap_in_reach:
                assert _sketch_rank_error(samples, q) <= 0.05, q

else:

    def test_sketch_vs_exact_quantiles_property():
        require_or_skip_hypothesis()  # skip locally, hard-fail in CI


# ---------------------------------------------------------------------------
# engine._run_tail: the jitted remainder runner regression
# ---------------------------------------------------------------------------


def _oracle_step_and_state():
    wl = synthetic_trace(
        num_jobs=12, tasks_per_job=4, task_duration=1.0, load=0.7,
        num_workers=32, seed=5,
    )
    from repro.simx.state import export_workload

    cfg = SimxConfig(num_workers=32, num_gms=GMS, num_lms=LMS)
    tasks = export_workload(wl)
    r = rt.get_rule("oracle")
    step = r.build_step(cfg, tasks, 0, match_fn=None, pick_fn=None,
                        faults=None, telemetry=False)
    return step, r.init(cfg, tasks)


def test_run_tail_matches_eager_scan():
    """A final partial chunk routed through the jitted ``_run_tail`` is
    bitwise the eager ``scan_rounds`` it replaced."""
    step, s0 = _oracle_step_and_state()
    for n in (1, 7, 23):
        eager = rt.scan_rounds(step, s0, n)
        jitted, done = engine._run_tail(step, s0, n)
        for a, b in zip(
            jax_leaves(eager), jax_leaves(jitted)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert bool(done) == bool(np.all(
            np.asarray(jitted.task_finish) <= float(jitted.t)))


def test_run_to_completion_budget_exact_through_tail():
    """``max_rounds`` not a multiple of ``chunk`` ends on the jitted tail
    at exactly the budget — same state as one eager scan of the budget."""
    step, s0 = _oracle_step_and_state()
    budget = 37  # chunk 16 -> 16 + 16 + tail of 5
    via_chunks = engine.run_to_completion(
        step, s0, chunk=16, max_rounds=budget)
    eager = rt.scan_rounds(step, s0, budget)
    assert float(via_chunks.t) == float(eager.t)
    np.testing.assert_array_equal(
        np.asarray(via_chunks.task_finish), np.asarray(eager.task_finish))


def jax_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)
