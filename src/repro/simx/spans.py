"""Host spans and compile counters of the simulator's own set-up.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` (it shares a device
trace's clock) that also adds its host seconds to ``totals[name]``, and
the compile seconds that ran inside it to ``compile_in[name]``.  The rule
builders open ``span("simx.build")`` around their host-side tables.

Importing ``repro.simx`` registers one ``jax.monitoring`` listener that
counts JAX's compile pipeline by phase and function: ``compile_s[(phase,
fun_name)]`` for the phases ``trace`` (jaxpr tracing), ``lower`` (jaxpr to
MLIR) and ``backend`` (XLA compile), with their times in ``events``, and
the persistent cache's ``cache["hits"]`` / ``cache["misses"]``.

``Program`` wraps a jitted function so that its optimized HLO can be read
after it ran (``hlo_text``), to attribute a device trace's ops to the
named scopes of the round pipeline; ``programs[name]`` holds the latest
one built under each name, and its ``last_call`` the ``perf_counter``
time it was last called (``phase_s(phase, until=...)`` counts the compiles
before it).
"""

from __future__ import annotations

import collections
import contextlib
import time

import jax

totals: collections.Counter = collections.Counter()
compile_s: collections.Counter = collections.Counter()
compile_in: collections.Counter = collections.Counter()
events: list = []           # (perf_counter time, phase, seconds)
cache = {"hits": 0, "misses": 0}
programs: dict = {}

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_open: collections.Counter = collections.Counter()
_muted = [False]


@contextlib.contextmanager
def span(name: str):
    """Time the block on the host and in the profiler's trace; an inner
    span of an open name is not counted twice."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        _open[name] += 1
        try:
            yield
        finally:
            _open[name] -= 1
            if not _open[name]:
                totals[name] += time.perf_counter() - t0


def phase_s(phase: str, until: float | None = None) -> float:
    """Seconds of one compile phase, summed over every function; with
    ``until``, only those that ended by that ``perf_counter`` time."""
    if until is None:
        return sum(s for (p, _), s in compile_s.items() if p == phase)
    return sum(s for t, p, s in events if p == phase and t <= until)


def _duration(event: str, secs: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None or _muted[0]:
        return
    fun = kw.get("fun_name", "")
    compile_s[(phase, fun)] += secs
    events.append((time.perf_counter(), phase, secs))
    for name, n in _open.items():
        if n:
            compile_in[name] += secs


def _event(event: str, **_) -> None:
    if _muted[0]:
        return
    if event == "/jax/compilation_cache/cache_hits":
        cache["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        cache["misses"] += 1


jax.monitoring.register_event_duration_secs_listener(_duration)
jax.monitoring.register_event_listener(_event)


def _spec(x):
    """The type a call was compiled for: committed arrays keep their
    sharding, so that lowering it again finds the compiled program."""
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=x.sharding if x.committed else None)
    return x


class Program:
    """A jitted function that keeps the argument types of its first call."""

    def __init__(self, name: str, fn):
        self.fn, self.args, self.last_call = fn, None, None
        programs[name] = self

    def __call__(self, *args):
        if self.args is None:
            self.args = jax.tree.map(_spec, args)
        self.last_call = time.perf_counter()
        return self.fn(*args)

    def __getattr__(self, attr):
        return getattr(self.fn, attr)

    def hlo_text(self) -> str | None:
        """The optimized HLO it runs (``None`` before its first call).
        The compiled program is found again, and the tracing it takes is
        left out of the counters."""
        if self.args is None:
            return None
        _muted[0] = True
        try:
            return self.fn.lower(*self.args).compile().as_text()
        finally:
            _muted[0] = False
