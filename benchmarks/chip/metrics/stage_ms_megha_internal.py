"""Device milliseconds per simulated round of one datacenter spent in
megha's internal match: FIFO windows, the ``[G, W/G]`` rank-and-select,
LM verification, piggybacks (``simx.megha.internal``): op seconds of the
traced window attributed by ``stages.stage_s``, over the rounds times
the datacenters (profiler trace and the runner's optimized HLO)."""

import stages


def read(w):
    return stages.stage_ms(w, "simx.megha.internal")
