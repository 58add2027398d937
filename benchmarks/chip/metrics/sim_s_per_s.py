"""Simulated datacenter seconds per wall second: every round the window
ran, times the stated ``dt``, times the datacenters each round advanced,
over the window's wall seconds (host clock, from the window's start until
every unit of work it sent has finished)."""


def read(w):
    return w.rounds * w.dt * w.datacenters / w.window_s
