"""Device milliseconds per simulated round of one datacenter spent in the
ops no attribution rule places in a stage (loop-carry copies, the scan's
counter, hoisted broadcasts): op seconds of the traced window attributed
by ``stages.stage_s``, over the rounds times the datacenters (profiler
trace and the runner's optimized HLO)."""

import stages


def read(w):
    return stages.stage_ms(w, "")
