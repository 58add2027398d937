"""Hypothesis property suite for the open-loop arrival generators
(``repro.workload.synth``): empirical rates agree with the declared
``mean_rate`` within CLT confidence bounds, the diurnal thinning
integrates to the offered load over whole periods, and every process is
deterministic per seed.  (Shape invariants and validation edges live in
the unguarded ``test_workload_arrivals``; this module follows the repo's
hypothesis idiom — skipped locally when hypothesis is absent, hard
required in CI via REQUIRE_HYPOTHESIS.)
"""

import itertools
import math

from conftest import require_or_skip_hypothesis

require_or_skip_hypothesis()
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.workload.synth import (  # noqa: E402
    DiurnalArrivals,
    MMPPArrivals,
    PhasedArrivals,
    PoissonArrivals,
)


def _span(proc, n):
    jobs = list(itertools.islice(proc.jobs(), n))
    return jobs, jobs[-1].submit_time - jobs[0].submit_time


@settings(max_examples=10, deadline=None)
@given(rate=st.floats(0.5, 20.0), seed=st.integers(0, 2**31 - 1))
def test_poisson_empirical_rate_within_ci(rate, seed):
    """Over N exponential gaps the mean IAT estimator has sd 1/(rate
    sqrt(N)) — the empirical mean must sit within 5 sigma of 1/rate."""
    n = 400
    jobs, span = _span(PoissonArrivals(rate=rate, seed=seed), n)
    mean_iat = span / (n - 1)
    assert abs(mean_iat - 1.0 / rate) <= 5.0 / (rate * math.sqrt(n - 1))


@settings(max_examples=8, deadline=None, derandomize=True)
@given(
    rate=st.floats(0.5, 10.0),
    d0=st.floats(1.0, 20.0),
    d1=st.floats(1.0, 20.0),
    seed=st.integers(0, 2**31 - 1),
)
# the dwell-weighted mean (r*d0 + r*d1) / (d0 + d1) rounds to one ulp
# below r here: the rate is right, exact float equality is not
@example(rate=1.8861860137670954, d0=1.0, d1=1.5, seed=0)
def test_mmpp_equal_rates_degenerate_to_poisson(rate, d0, d1, seed):
    """With equal regime rates the MMPP IS a homogeneous Poisson process
    whatever the dwell times — the regime-crossing IAT accounting must
    preserve each exponential gap exactly, so this is the sharp
    regression for the dropped-dwell bug (which biased the rate even in
    the degenerate case)."""
    n = 600
    proc = MMPPArrivals(rates=(rate, rate), dwell=(d0, d1), seed=seed)
    assert math.isclose(proc.mean_rate, rate, rel_tol=1e-12, abs_tol=0.0)
    _, span = _span(proc, n)
    mean_iat = span / (n - 1)
    assert abs(mean_iat - 1.0 / rate) <= 5.0 / (rate * math.sqrt(n - 1))


@settings(max_examples=6, deadline=None)
@given(
    calm=st.floats(0.5, 4.0),
    burst_mult=st.floats(2.0, 10.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_mmpp_long_run_rate(calm, burst_mult, seed):
    """The empirical long-run rate sits inside the MMPP's own CI: count
    variance over k cycles is k * sum(rate_i^2 dwell_i^2) from the
    exponential dwell randomness plus the Poisson term n — NOT sqrt(n),
    which is why the bound is derived, not guessed."""
    d = (20.0, 10.0)
    rates = (calm, calm * burst_mult)
    proc = MMPPArrivals(rates=rates, dwell=d, seed=seed)
    n = 1500
    _, span = _span(proc, n)
    emp = (n - 1) / span
    cycles = (n / proc.mean_rate) / sum(d)
    var = cycles * sum(r * r * dd * dd for r, dd in zip(rates, d)) + n
    tol = 6.0 * math.sqrt(var) / n  # relative, 6 sigma
    assert abs(emp - proc.mean_rate) <= tol * proc.mean_rate


@settings(max_examples=6, deadline=None)
@given(
    base=st.floats(2.0, 10.0),
    amp=st.floats(0.0, 0.9),
    seed=st.integers(0, 2**31 - 1),
)
def test_diurnal_integral_matches_offered_load(base, amp, seed):
    """Counting arrivals over whole periods: the sinusoid integrates out,
    so E[N(k periods)] = base_rate * k * period; Poisson sd sqrt(N)."""
    period = 40.0
    proc = DiurnalArrivals(
        base_rate=base, amplitude=amp, period=period, seed=seed
    )
    horizon = 10 * period
    count = 0
    for j in proc.jobs():
        if j.submit_time > horizon:
            break
        count += 1
    expect = base * horizon
    assert abs(count - expect) <= 5.0 * math.sqrt(expect)
    # offered_load is the rate scaled by exact fixed-shape demand
    assert proc.offered_load(1000) == (
        proc.mean_rate * proc.mean_job_demand() / 1000
    )


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), rate=st.floats(0.5, 10.0))
def test_generators_deterministic_per_seed(seed, rate):
    """Same seed => bit-identical stream; different seed => different
    stream (the streamed-chunk determinism pin's generator half)."""
    mk = lambda s: PhasedArrivals(  # noqa: E731
        [(8.0, rate), (4.0, 3.0 * rate)], cycle=True, seed=s
    )
    a = [(j.submit_time, tuple(j.durations))
         for j in itertools.islice(mk(seed).jobs(), 50)]
    b = [(j.submit_time, tuple(j.durations))
         for j in itertools.islice(mk(seed).jobs(), 50)]
    c = [(j.submit_time, tuple(j.durations))
         for j in itertools.islice(mk(seed + 1).jobs(), 50)]
    assert a == b
    assert a != c
