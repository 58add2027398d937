"""End-to-end behaviour tests for the whole system."""

import math
import subprocess
import sys

import numpy as np
import pytest

from repro.sim.simulator import run_simulation
from repro.workload.synth import (
    downsampled,
    google_like_trace,
    synthetic_trace,
    yahoo_like_trace,
)


def test_prototype_style_comparison_megha_vs_pigeon():
    """§5.3 (Fig. 4): on the down-sampled traces Megha's delays are bounded
    while Pigeon shows a long tail."""
    base = google_like_trace(num_jobs=800, total_tasks=4000, load=0.8,
                             num_workers=480, seed=4)
    wl = downsampled(base, factor=4, mean_iat=0.05, seed=4)
    megha = run_simulation("megha", wl, num_workers=480,
                           num_gms=3, num_lms=3, heartbeat_interval=10.0)
    pigeon = run_simulation("pigeon", wl, num_workers=480)
    sm, sp = megha.summary(), pigeon.summary()
    assert sm["all_median_delay"] <= sp["all_median_delay"] + 1e-9
    assert sm["all_p95_delay"] <= sp["all_p95_delay"] + 1e-9


#: Each Table 1 generator at its own DC size, cut to 500 jobs of 20,000
#: tasks in all: (generator, DC size, keyword arguments)
TABLE1 = {
    "yahoo": (yahoo_like_trace, 3000, dict(total_tasks=20000, seed=1)),
    "google": (google_like_trace, 13000, dict(total_tasks=20000, seed=2)),
    "synthetic": (synthetic_trace, 10000, dict(tasks_per_job=40, seed=3)),
}


@pytest.mark.parametrize("gen", sorted(TABLE1))
def test_workload_statistics_match_table1_scale(gen):
    make, workers, kw = TABLE1[gen]
    wl = make(num_jobs=500, load=0.8, num_workers=workers, **kw)
    s = wl.stats()
    assert s["num_jobs"] == 500
    assert abs(s["num_tasks"] - 20000) <= 1
    # effective load ~0.8 given span ~ num_jobs * mean_iat
    span = max(j.submit_time for j in wl.jobs)
    load = s["demand_resource_seconds"] / (span * workers)
    assert 0.5 < load < 1.3


def test_delay_decomposition_accounts_for_total():
    """Eq. 5: the recorded components must sum to the task delay."""
    wl = yahoo_like_trace(num_jobs=60, total_tasks=700, load=0.7,
                          num_workers=256, seed=9)
    for sched in ("megha", "pigeon"):
        m = run_simulation(sched, wl, num_workers=256)
        for t in m.tasks:
            if math.isnan(t.finish_time):
                continue
            assert t.decomposition_residual() < 1e-9, (sched, t)


def test_serve_cli_end_to_end():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--requests", "50", "--pods", "2", "--slots", "8",
         "--frontends", "2"],
        capture_output=True, text=True, env=_env(), timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "requests=50/50" in out.stdout


def _env():
    import os

    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    return env
