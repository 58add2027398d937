"""Device busy milliseconds per simulated round of one datacenter: the
busy time of every chip the cell uses, summed, over the rounds times the
datacenters the traced window advanced (profiler trace)."""


def read(w):
    red = w.reduced
    if red is None or not red.busy_s or not w.rounds:
        return None
    return 1e3 * sum(red.busy_s.values()) / (w.rounds * w.datacenters)
