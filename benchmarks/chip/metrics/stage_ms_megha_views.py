"""Device milliseconds per simulated round of one datacenter spent in
megha's view upkeep: completions regained, the heartbeat
(``simx.megha.views``): op seconds of the traced window attributed by
``stages.stage_s``, over the rounds times the datacenters (profiler
trace and the runner's optimized HLO)."""

import stages


def read(w):
    return stages.stage_ms(w, "simx.megha.views")
