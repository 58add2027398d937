"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses (profiler trace)."""


def read(w):
    red = w.reduced
    if red is None or not red.busy_s:
        return None
    return 1.0 - red.busy_mean_s / red.window_s
