"""How unevenly the mesh is loaded: the most minus the fewest busy seconds
of the cell's chips in the traced window, over their mean (profiler
trace, one busy time per TPU plane)."""


def read(w):
    red = w.reduced
    if red is None or not red.busy_s or not red.busy_mean_s:
        return None
    busy = red.busy_s.values()
    return (max(busy) - min(busy)) / red.busy_mean_s
