"""Seconds from the start of the process to the first timed round:
imports, TPU start-up, trace generation, building the program, compiling
or reading it from the persistent cache, and one warm unit of work."""


def read(w):
    return w.setup_s
