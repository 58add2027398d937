"""Device milliseconds per simulated round of one datacenter spent in
megha's borrow pass (``simx.megha.borrow``) by the batched grid runner,
where the pass's ``lax.cond`` has a predicate per point and so runs in
every round: op seconds of the traced window attributed by
``stages.stage_s``, over the rounds times the datacenters (profiler trace
and the runner's optimized HLO)."""

import stages


def read(w):
    return stages.stage_ms(w, "simx.megha.borrow")
