"""In-scan telemetry for the simx matrix: per-round time series inside jit.

The paper's thesis is not just lower job delay — Megha buys fast decisions
with *eventual consistency*, paying in inconsistency-repair traffic and
messaging overhead that the other architectures pay as probe/queue
waiting.  Terminal p50/p95 numbers can't show those mechanisms at work;
this module makes them observable without leaving the compiled program:

  * ``TelemetryConfig`` — static knobs: the decimation ``stride`` (one
    series sample per ``stride`` rounds) and the fixed-bin delay-histogram
    shape.  Hashable, so it is safe as a closure/static argument.
  * ``Timeline`` — the collected pytree: a time axis ``t[K]``, a dict of
    ``[K]`` series (per-window counter deltas + end-of-window gauges), and
    the in-jit job-delay histogram ``delay_hist[B]``.  Carried memory is
    O(rounds / stride + bins) by construction — the inner per-round scan
    emits scalars that are summed per window before they ever stack.
  * ``scan_rounds_telemetry`` — the decimated nested-scan driver: an outer
    ``lax.scan`` over ``num_rounds // stride`` windows, each window an
    inner scan of ``stride`` telemetry-enabled round steps (built by
    ``runtime.compose_step(..., telemetry=True)``, which returns
    ``(state, counters)`` per round).  Fully traceable: a sweep can vmap
    it over seeds/loads like any other ``simulate_fixed`` call.
  * ``to_chrome_trace`` — serialize a ``Timeline`` to the Chrome trace
    event format (counter events, ``"ph": "C"``), viewable in
    ``chrome://tracing`` / Perfetto; ``bench_simx.py --trace`` drives it.

The round-step contract (see ``runtime.compose_step``): a rule's dispatch
MAY return a ``"telemetry"`` key — a dict of per-round int32 scalar
counters (``launches`` expected of every rule, plus rule-specific extras:
megha ``view_repairs``, eagle ``sss_rejections``, pigeon
``reserve_hits``, sparrow and eagle ``full_width_rounds``) — and the
runtime adds the per-round deltas of the shared ``CoreState`` counters
(messages, probes, inconsistencies, lost, and the reservation-queue
health counters for ``QueueState`` rules).
With telemetry disabled the key is never built and the step compiles to
exactly today's program — final states are pinned bitwise-identical by
``tests/test_simx_telemetry.py``.

**Streaming quantile sketches** (the steady-state engine,
``repro.simx.stream``): a drain-to-empty run can afford one terminal
``jnp.sort`` over the ``[J]`` delay vector, but a steady-state run retires
jobs continuously and must never materialize all delays at once.
``QuantileSketch`` is a fixed-state P² sketch (Jain & Chlamtac 1985, one
5-marker cell per target quantile, vmap-shaped ``[Q, 5]`` state) updated
in-jit per retired job: O(Q) memory independent of how many delays it has
absorbed.  Error contract: the P² estimate tracks the *rank* of the true
quantile — for >= 1000 absorbed samples from a continuous distribution,
the empirical CDF evaluated at the estimate is within +-0.05 of the
target quantile (pinned as a hypothesis property in
``tests/test_simx_streaming.py``); with fewer than 5 samples the sketch
falls back to exact order statistics of its warm-up buffer.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx import runtime
from repro.simx.faults import FaultSchedule, worker_dead
from repro.simx.state import SimxConfig, TaskArrays, spec


@dataclass(frozen=True)
class TelemetryConfig:
    """Static telemetry parameters (hashable: safe to close over / pass as
    a jit static argument).

    ``stride`` decimates the series: one sample per ``stride`` rounds —
    counter keys hold the *sum over the window*, gauge keys the value at
    the window's end.  ``delay_bins`` x ``delay_max`` shape the in-jit
    job-delay histogram (bin width ``delay_max / delay_bins``; delays past
    ``delay_max`` clamp into the last bin, unfinished jobs are excluded).
    """

    stride: int = 8
    delay_bins: int = 32
    delay_max: float = 60.0

    def __post_init__(self) -> None:
        if self.stride < 1:
            raise ValueError("telemetry stride must be >= 1")
        if self.delay_bins < 1:
            raise ValueError("delay_bins must be >= 1")

    @property
    def bin_width(self) -> float:
        return self.delay_max / self.delay_bins


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Timeline:
    """One simulation's collected telemetry (a pytree: vmapped sweeps
    stack a leading grid axis onto every leaf).

    ``series`` keys split into *counters* (per-window sums of per-round
    deltas: ``launches``, ``messages``, ``probes``, ``inconsistencies``,
    ``lost``, rule extras, and — for reservation-queue rules —
    ``res_overflow`` / ``probe_lag``) and *gauges* sampled at each
    window's end (``utilization`` in [0, 1], ``pending`` / ``running`` /
    ``completed`` task counts, ``queue_depth`` = jobs with pending work,
    ``live_workers``).  ``t[k]`` is the simulated time at the END of
    window k; window k covers rounds ``[k * stride, (k+1) * stride)``.
    A trailing partial window (``num_rounds % stride`` rounds) advances
    the state but is not sampled — cumulative totals still appear in the
    final state's counters.
    """

    t: jax.Array = spec("float32[K]")  # simulated time per sample
    series: dict                       # str -> [K] array (counters + gauges);
                                       # dict-valued, so no per-field spec
    delay_hist: jax.Array = spec("int32[B]")  # finished-job delay histogram
    stride: int = dataclasses.field(metadata=dict(static=True), default=1)
    dt: float = dataclasses.field(metadata=dict(static=True), default=0.05)
    delay_max: float = dataclasses.field(metadata=dict(static=True), default=60.0)

    @property
    def num_samples(self) -> int:
        return int(self.t.shape[-1])

    @property
    def bin_edges(self) -> np.ndarray:
        """float64[B + 1] — delay-histogram bin edges (last bin clamps)."""
        b = self.delay_hist.shape[-1]
        return np.linspace(0.0, self.delay_max, b + 1)

    def to_chrome_trace(
        self, pid: int = 1, process_name: Optional[str] = None
    ) -> dict:
        """Serialize to the Chrome trace event format: one counter track
        (``"ph": "C"``) per series key, timestamps in microseconds of
        simulated time.  The returned dict dumps straight to a JSON file
        loadable in ``chrome://tracing`` / Perfetto (object format, a
        ``traceEvents`` list)."""
        ts = np.asarray(self.t, np.float64) * 1e6          # sim-seconds -> us
        events: list[dict] = []
        if process_name is not None:
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": process_name},
            })
        for key in sorted(self.series):
            vals = np.asarray(self.series[key], np.float64)
            for k in range(vals.shape[-1]):
                events.append({
                    "name": key, "ph": "C", "pid": pid, "tid": 0,
                    "ts": float(ts[k]), "args": {key: float(vals[k])},
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# provenance span tracing (Chrome "X" duration events)
# ---------------------------------------------------------------------------


#: tid offset for per-worker execution tracks (GM/scheduler queue tracks
#: sit at ``1 + gm``; workers at ``WORKER_TID_BASE + worker``) — keeping
#: the mapping static makes traces from different runs line up.
WORKER_TID_BASE = 1000


def provenance_spans(
    prov,
    state,
    tasks: TaskArrays,
    cfg: SimxConfig,
    pid: int = 1,
    name: Optional[str] = None,
    max_tasks: Optional[int] = None,
) -> list[dict]:
    """Chrome trace duration events (``"ph": "X"``) from a run's
    ``Provenance`` (``repro.simx.provenance``).

    Each finished task contributes two spans:

      * a **wait** span on the placing scheduler's track (``tid = 1 + gm``,
        gm from ``placed_gm``) covering submit -> launch — the queueing the
        decomposition splits into components;
      * a **run** span on the placed worker's track
        (``tid = WORKER_TID_BASE + worker``) covering start -> finish.

    Thread-name metadata events label both track families, so the pid/tid
    mapping is self-describing; timestamps are microseconds of simulated
    time, matching ``Timeline.to_chrome_trace`` counter tracks (emit both
    under one pid to overlay them).  ``max_tasks`` truncates to the first N
    tasks (trace viewers choke far before the arrays do).
    """
    from repro.simx.provenance import UNSET

    tf = np.asarray(state.task_finish, np.float64)
    end_t = float(state.t)
    dur = np.asarray(tasks.duration, np.float64)
    sub = np.asarray(tasks.submit, np.float64)
    job = np.asarray(tasks.job)
    launch_r = np.asarray(prov.launch_round)
    gm = np.asarray(prov.placed_gm)
    worker = np.asarray(prov.placed_worker)
    requeue = np.asarray(prov.requeue_count)
    stale = np.asarray(prov.stale_retry_count)
    done = (tf <= end_t) & (launch_r != UNSET) & (worker != UNSET)
    ids = np.nonzero(done)[0]
    if max_tasks is not None:
        ids = ids[:max_tasks]

    events: list[dict] = []
    if name is not None:
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": name},
        })
    for g in sorted({int(gm[i]) for i in ids} | ({0} if not ids.size else set())):
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": 1 + g,
            "args": {"name": f"gm{g}"},
        })
    for w in sorted({int(worker[i]) for i in ids}):
        events.append({
            "name": "thread_name", "ph": "M", "pid": pid,
            "tid": WORKER_TID_BASE + w, "args": {"name": f"worker{w}"},
        })
    for i in ids:
        start = tf[i] - dur[i]                      # recorded at launch
        label = f"job{int(job[i])}/task{int(i)}"
        args = {
            "job": int(job[i]), "task": int(i),
            "requeues": int(requeue[i]), "stale_retries": int(stale[i]),
        }
        wait = max(0.0, start - sub[i])
        events.append({
            "name": f"{label} wait", "ph": "X", "pid": pid,
            "tid": 1 + int(gm[i]), "ts": sub[i] * 1e6, "dur": wait * 1e6,
            "args": args,
        })
        events.append({
            "name": label, "ph": "X", "pid": pid,
            "tid": WORKER_TID_BASE + int(worker[i]),
            "ts": start * 1e6, "dur": dur[i] * 1e6, "args": args,
        })
    events.sort(key=lambda e: (e["ph"] != "M", e.get("ts", 0.0)))
    return events


# ---------------------------------------------------------------------------
# shared gauges + the delay histogram (all in-jit)
# ---------------------------------------------------------------------------


def default_sample_fn(
    cfg: SimxConfig,
    tasks: TaskArrays,
    faults: Optional[FaultSchedule] = None,
) -> Callable:
    """Build the gauge sampler the decimated scan runs at each window end:
    the scheduler-independent observables every rule shares, derived from
    the carried state alone (no per-round bookkeeping needed).  With
    ``faults``, dead workers are excluded from utilization and counted
    out of ``live_workers``."""
    W = cfg.num_workers
    J = tasks.num_jobs

    def sample(s) -> dict:
        busy = s.worker_finish > s.t                       # bool[W]
        if faults is not None:
            dead = worker_dead(faults, s.t)
            busy = busy & ~dead                            # down != working
            live = jnp.int32(W) - jnp.sum(dead, dtype=jnp.int32)
        else:
            live = jnp.int32(W)
        done = s.task_finish <= s.t
        launched = ~jnp.isinf(s.task_finish)
        pend = ~launched & (tasks.submit <= s.t)           # arrived, unlaunched
        pend_job = jnp.zeros(J, jnp.bool_).at[tasks.job].max(pend)
        return {
            "utilization": jnp.sum(busy, dtype=jnp.float32) / jnp.float32(W),
            "pending": jnp.sum(pend, dtype=jnp.int32),
            "running": jnp.sum(launched & ~done, dtype=jnp.int32),
            "completed": jnp.sum(done, dtype=jnp.int32),
            "queue_depth": jnp.sum(pend_job, dtype=jnp.int32),
            "live_workers": live,
        }

    return sample


def delay_histogram(
    task_finish: jax.Array, t: jax.Array, tasks: TaskArrays, tel: TelemetryConfig
) -> jax.Array:
    """int32[delay_bins] — fixed-bin histogram of finished-job delays
    (Eq. 2, via the runtime's shared reduction), computed in-jit from the
    final state.  Delays are recorded at completion and never change, so
    one end-of-run binning matches an in-scan accumulation exactly; delays
    past ``delay_max`` clamp into the last bin, unfinished jobs drop."""
    delays, _ = runtime.job_delays_from_state(task_finish, t, tasks)
    b = tel.delay_bins
    idx = jnp.floor(delays / tel.bin_width).astype(jnp.int32)
    idx = jnp.where(jnp.isfinite(delays), jnp.clip(idx, 0, b - 1), b)
    return jnp.zeros(b, jnp.int32).at[idx].add(1, mode="drop")


# ---------------------------------------------------------------------------
# streaming quantile sketch (P², in-jit, fixed state)
# ---------------------------------------------------------------------------

#: default steady-state reporting quantiles (median + the tail family)
DEFAULT_QUANTILES = (0.5, 0.95, 0.99, 0.999)

#: marker-fraction template: desired marker positions after n observations
#: are ``1 + (n - 1) * frac`` with frac = [0, p/2, p, (1 + p)/2, 1]
def _marker_fracs(targets: tuple) -> np.ndarray:
    p = np.asarray(targets, np.float32)[:, None]
    return np.concatenate(
        [np.zeros_like(p), p / 2, p, (1 + p) / 2, np.ones_like(p)], axis=1
    )


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class QuantileSketch:
    """P² streaming quantile state: one 5-marker cell per target quantile.

    Memory is O(len(targets)) — independent of how many observations have
    been absorbed — and every update is a fixed-shape in-jit step, so the
    sketch rides inside ``lax.scan`` segments and vmaps like any pytree.
    The first 5 observations fill ``buf`` (exact order statistics); the
    5th bootstraps the markers, after which the classic P² marker-
    adjustment recursion runs (parabolic prediction, linear fallback,
    integer marker positions with the gap >= 1 invariant, so none of the
    divided differences can hit a zero denominator).
    """

    q: jax.Array = spec("float32[Q, 5]")    # marker heights
    n: jax.Array = spec("float32[Q, 5]")    # integer marker pos (1-based)
    npd: jax.Array = spec("float32[Q, 5]")  # desired marker positions
    dn: jax.Array = spec("float32[Q, 5]")   # per-obs desired increment
    buf: jax.Array = spec("float32[5]")     # warm-up buffer (first 5 obs)
    count: jax.Array = spec("int32[]")      # observations absorbed
    targets: tuple = dataclasses.field(
        metadata=dict(static=True), default=DEFAULT_QUANTILES
    )


def sketch_init(targets: tuple = DEFAULT_QUANTILES) -> QuantileSketch:
    """A fresh sketch for ``targets`` (a static tuple of quantiles in
    (0, 1)).  Marker positions start at their bootstrap values so the
    update recursion is well-defined (no zero gaps) even while the
    warm-up buffer is still filling."""
    if not targets or min(targets) <= 0.0 or max(targets) >= 1.0:
        raise ValueError("quantile targets must lie strictly in (0, 1)")
    fr = _marker_fracs(tuple(targets))
    qn = fr.shape[0]
    return QuantileSketch(
        q=jnp.zeros((qn, 5), jnp.float32),
        n=jnp.broadcast_to(jnp.arange(1.0, 6.0, dtype=jnp.float32), (qn, 5)),
        npd=jnp.asarray(1.0 + 4.0 * fr, jnp.float32),
        dn=jnp.asarray(fr, jnp.float32),
        buf=jnp.zeros(5, jnp.float32),
        count=jnp.int32(0),
        targets=tuple(targets),
    )


def _p2_markers(q, n, npd, dn, x):
    """One classic P² marker-adjustment step for observation ``x`` on
    already-bootstrapped ``[Q, 5]`` marker state."""
    q = q.at[:, 0].min(x)                                  # new minimum
    q = q.at[:, 4].max(x)                                  # new maximum
    # cell index k in [0, 3]: number of markers <= x, shifted/clipped
    k = jnp.clip(jnp.sum(q <= x, axis=1) - 1, 0, 3)        # int[Q]
    n = n + (jnp.arange(5)[None, :] > k[:, None])          # shift suffix
    npd = npd + dn
    # adjust the three interior markers in order (the sequential sweep is
    # part of the algorithm: marker i's move sees i-1's updated position)
    for i in (1, 2, 3):
        d = npd[:, i] - n[:, i]
        gap_up = n[:, i + 1] - n[:, i]
        gap_dn = n[:, i - 1] - n[:, i]
        move = jnp.where(
            (d >= 1.0) & (gap_up > 1.0), 1.0,
            jnp.where((d <= -1.0) & (gap_dn < -1.0), -1.0, 0.0),
        )
        qi, qu, ql = q[:, i], q[:, i + 1], q[:, i - 1]
        ni, nu, nl = n[:, i], n[:, i + 1], n[:, i - 1]
        q_par = qi + move / (nu - nl) * (
            (ni - nl + move) * (qu - qi) / (nu - ni)
            + (nu - ni - move) * (qi - ql) / (ni - nl)
        )
        q_lin = qi + move * jnp.where(
            move >= 0.0, (qu - qi) / (nu - ni), (ql - qi) / (nl - ni)
        )
        q_new = jnp.where(
            move != 0.0,
            jnp.where((ql < q_par) & (q_par < qu), q_par, q_lin),
            qi,
        )
        q = q.at[:, i].set(q_new)
        n = n.at[:, i].set(ni + move)
    return q, n, npd


def sketch_update(sk: QuantileSketch, x: jax.Array, valid) -> QuantileSketch:
    """Absorb one observation ``x`` (a float scalar) when ``valid``; with
    ``valid`` false the state passes through untouched (so masked batch
    updates compose under ``lax.scan``)."""
    x = jnp.asarray(x, jnp.float32)
    cnt = sk.count
    buf = jnp.where(cnt < 5, sk.buf.at[jnp.clip(cnt, 0, 4)].set(x), sk.buf)
    # bootstrap (exactly at the 5th observation): sorted buffer -> markers
    boot_q = jnp.broadcast_to(jnp.sort(buf), sk.q.shape)
    # steady update (safe pre-bootstrap: positions init at 1..5, no 0 gaps)
    q2, n2, npd2 = _p2_markers(sk.q, sk.n, sk.npd, sk.dn, x)
    is_boot = cnt == 4
    is_run = cnt >= 5
    new = QuantileSketch(
        q=jnp.where(is_boot, boot_q, jnp.where(is_run, q2, sk.q)),
        n=jnp.where(is_run, n2, sk.n),
        npd=jnp.where(is_run, npd2, sk.npd),
        dn=sk.dn,
        buf=buf,
        count=cnt + 1,
        targets=sk.targets,
    )
    valid = jnp.asarray(valid)
    merged = jax.tree.map(
        lambda a, b: jnp.where(valid, a, b),
        (new.q, new.n, new.npd, new.buf, new.count),
        (sk.q, sk.n, sk.npd, sk.buf, sk.count),
    )
    return QuantileSketch(
        q=merged[0], n=merged[1], npd=merged[2], dn=sk.dn,
        buf=merged[3], count=merged[4], targets=sk.targets,
    )


def sketch_absorb(
    sk: QuantileSketch, values: jax.Array, mask: jax.Array
) -> QuantileSketch:
    """Absorb a batch: ``values[i]`` is observed iff ``mask[i]`` — the
    per-segment bulk update (``lax.scan`` over the batch, fixed state)."""
    values = jnp.asarray(values, jnp.float32)

    def body(s, xv):
        x, v = xv
        return sketch_update(s, x, v), None

    sk, _ = jax.lax.scan(body, sk, (values, jnp.asarray(mask)))
    return sk


def sketch_quantiles(sk: QuantileSketch) -> jax.Array:
    """float32[Q] — the current quantile estimates (P² center markers;
    exact order statistics of the warm-up buffer below 5 observations;
    NaN with zero observations)."""
    cnt = sk.count
    p = jnp.asarray(sk.targets, jnp.float32)
    # small-sample path: nearest-rank on the sorted valid prefix of buf
    pad = jnp.where(jnp.arange(5) < cnt, sk.buf, jnp.inf)
    small = jnp.sort(pad)[
        jnp.clip(jnp.round(p * (cnt - 1)).astype(jnp.int32), 0, 4)
    ]
    est = jnp.where(cnt >= 5, sk.q[:, 2], small)
    return jnp.where(cnt > 0, est, jnp.nan)


# ---------------------------------------------------------------------------
# the decimated nested-scan driver
# ---------------------------------------------------------------------------


def advance_plain(step: Callable, state, num_rounds: int):
    """Advance a telemetry-enabled step (returns ``(state, counters)``)
    ``num_rounds`` rounds, discarding the counters — the trailing
    partial-window / exact-``max_rounds`` path."""
    state, _ = jax.lax.scan(
        lambda s, _: (step(s)[0], None), state, None, length=num_rounds
    )
    return state


def scan_blocks(
    step: Callable, state, num_blocks: int, stride: int, sample_fn: Callable
):
    """The decimation core: ``num_blocks`` windows of ``stride`` rounds
    each under one outer ``lax.scan``.  Per window, the inner scan's
    per-round counter dicts are tree-summed to one scalar per key (so the
    stacked ``ys`` are O(num_blocks), never O(rounds)), then the gauges
    are sampled from the window-end state.  Returns ``(state, series)``
    with ``series`` a dict of ``[num_blocks]`` arrays including ``"t"``."""

    def block(c, _):
        c, counters = jax.lax.scan(
            lambda c2, __: step(c2), c, None, length=stride
        )
        out = jax.tree.map(lambda v: jnp.sum(v, axis=0), counters)
        s = runtime.carry_state(c)
        out.update(sample_fn(s))
        out["t"] = s.t
        return c, out

    return jax.lax.scan(block, state, None, length=num_blocks)


def scan_rounds_telemetry(
    step: Callable,
    state,
    num_rounds: int,
    tel: TelemetryConfig,
    cfg: SimxConfig,
    tasks: TaskArrays,
    faults: Optional[FaultSchedule] = None,
) -> tuple:
    """Telemetry counterpart of ``runtime.scan_rounds``: advance ``state``
    exactly ``num_rounds`` rounds collecting the decimated series, then
    bin the final job delays.  ``step`` must be telemetry-enabled
    (``compose_step(..., telemetry=True)``).  Returns
    ``(state, Timeline)`` — fully traceable, so sweeps vmap it."""
    K = num_rounds // tel.stride
    rem = num_rounds - K * tel.stride
    sample_fn = default_sample_fn(cfg, tasks, faults)
    state, series = scan_blocks(step, state, K, tel.stride, sample_fn)
    if rem:
        state = advance_plain(step, state, rem)
    t_axis = series.pop("t")
    s = runtime.carry_state(state)
    hist = delay_histogram(s.task_finish, s.t, tasks, tel)
    return state, Timeline(
        t=t_axis,
        series=series,
        delay_hist=hist,
        stride=tel.stride,
        dt=cfg.dt,
        delay_max=tel.delay_max,
    )
