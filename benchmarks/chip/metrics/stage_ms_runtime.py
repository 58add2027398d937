"""Device milliseconds per simulated round of one datacenter spent in the
runtime's own stages (the fault transition, the completion masks, the
clock and counter advance, the chunk's done flag): op seconds of the
traced window attributed by ``stages.stage_s``, over the rounds times
the datacenters (profiler trace and the runner's optimized HLO)."""

import stages


def read(w):
    return stages.stage_ms(w, *stages.RUNTIME)
