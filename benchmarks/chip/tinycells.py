"""Tiny stand-ins for the benchmark's cells, for its CPU tests.

pytest collects the benchmark's tests from the root of the checkout, so
each test module imports this one first: it puts the tests on the CPU and
the program on the path.  (A ``conftest.py`` here would be imported under
the same module name as ``tests/conftest.py``, which the repo's tests
import helpers from.)"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_HERE = Path(__file__).resolve().parent
for _p in (str(_HERE), str(_HERE.parents[1] / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: Tiny stand-ins for each cell, small enough for the CPU: the cell's own
#: configuration and traffic files, with fewer workers and jobs.
TINY = {
    "synth50k.megha": ("synth50k", "megha", {"num_workers": 512},
                       {"num_jobs": 12, "tasks_per_job": 64}, {}),
    "synth50k.sparrow": ("synth50k", "sparrow", {"num_workers": 512},
                         {"num_jobs": 12, "tasks_per_job": 64}, {}),
    "google13k.pigeon": ("google13k", "pigeon", {"num_workers": 400},
                         {"num_jobs": 150, "total_tasks": 3000},
                         {"chunk": 64}),
}


def tiny(name: str) -> tuple[dict, dict]:
    """``(configuration, traffic)`` of the tiny stand-in for cell
    ``name``."""
    import run

    conf, traf, cluster, trace, over = TINY[name]
    config = run.load_json(_HERE / "configs" / f"{conf}.json")
    traffic = run.load_json(_HERE / "traffic" / f"{traf}.json")
    config["cluster"].update(cluster)
    config["trace"].update(trace)
    traffic.update(over)
    return config, traffic


def tiny_run(name: str, *, seed: int = 2**31 + 77, seconds: float = 0.3,
             trace: bool = False, program_cluster=None) -> dict:
    """One CPU run of the tiny stand-in, past the harness's look for a
    chip; returns the result line's object."""
    import time

    import jax

    import run

    config, traffic = tiny(name)
    metrics = run.cell_metrics(
        name, run.load_json(run.ROOT / "BENCHMARK.json"), trace)
    return run.run_cell(config, traffic, metrics, seed=seed,
                        seconds=seconds, trace=trace,
                        devices=jax.devices()[:1],
                        program_cluster=program_cluster,
                        t0=time.perf_counter())
