"""Seconds of set-up spent in JAX's compile pipeline: jaxpr tracing,
lowering to MLIR and XLA's backend compile, summed over every program
compiled before the chunk runner's last call (the program's compile
counter, ``repro.simx.spans``; the window compiles nothing, and what the
reference compiles after it is left out)."""

import stages


def read(w):
    spans = stages.program_spans()
    if spans is None:
        return None
    until = getattr(spans.programs.get(stages.RUNNER), "last_call", None)
    return sum(spans.phase_s(p, until) for p in ("trace", "lower", "backend"))
