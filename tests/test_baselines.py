import collections
import math

import pytest

from repro.core import base
from repro.core.metrics import TaskRecord
from repro.sim.simulator import run_simulation
from repro.workload.synth import synthetic_trace, yahoo_like_trace
from repro.workload.traces import Job, Workload


WL = yahoo_like_trace(num_jobs=150, total_tasks=2500, load=0.7,
                      num_workers=512, seed=11)
# contended workload: the regime the paper's Fig. 3 claims concern
WL_HOT = yahoo_like_trace(num_jobs=250, total_tasks=4500, load=0.92,
                          num_workers=384, seed=12)


@pytest.mark.parametrize("name", ["sparrow", "eagle", "pigeon"])
def test_baseline_completes_all_jobs(name):
    m = run_simulation(name, WL, num_workers=512)
    unfinished = [j for j in m.jobs if math.isnan(j.finish_time)]
    assert not unfinished, f"{name}: {len(unfinished)} unfinished"
    assert len(m.tasks) == WL.num_tasks


def test_sparrow_probes_are_batch_sampled():
    wl = Workload("j", [Job(0, 0.0, [1.0] * 10)])
    m = run_simulation("sparrow", wl, num_workers=256, probe_ratio=2)
    assert m.probes == 20  # d * n


def test_megha_beats_baselines_on_trace():
    """Fig. 3: Megha records the lowest delays of the four architectures
    under load (uncontended, all near-zero-delay schedulers tie at the hop
    count, so the claim is evaluated on the contended workload)."""
    res = {
        n: run_simulation(n, WL_HOT, num_workers=384).summary()
        for n in ("megha", "sparrow", "eagle", "pigeon")
    }
    for other in ("sparrow", "eagle", "pigeon"):
        assert res["megha"]["all_mean_delay"] <= res[other]["all_mean_delay"] * 1.05, (
            other, res["megha"]["all_mean_delay"], res[other]["all_mean_delay"],
        )
    # Sparrow (pure sampling, d=2) is the worst performer (paper Fig. 3)
    assert res["sparrow"]["all_median_delay"] == max(
        r["all_median_delay"] for r in res.values()
    )


def test_eagle_short_jobs_avoid_long_nodes():
    """SSS: short jobs should see lower p95 than under Sparrow on a mixed
    workload (head-of-line blocking avoided)."""
    wl = yahoo_like_trace(num_jobs=120, total_tasks=1200, load=0.8,
                          num_workers=128, seed=5)
    sparrow = run_simulation("sparrow", wl, num_workers=128).summary()
    eagle = run_simulation("eagle", wl, num_workers=128).summary()
    assert eagle["short_p95_delay"] <= sparrow["short_p95_delay"]


def test_pigeon_reserved_workers_prioritize_short():
    wl = yahoo_like_trace(num_jobs=120, total_tasks=1200, load=0.9,
                          num_workers=128, seed=6)
    m = run_simulation("pigeon", wl, num_workers=128).summary()
    # short jobs must not fare worse than long jobs under priority queuing
    assert m["short_median_delay"] <= m["long_median_delay"] + 1e-9


class _CountedLaunches(TaskRecord):
    """A task record that counts its launches: every write of a real
    start time is one launch onto a worker."""

    def __setattr__(self, key, value):
        if key == "start_time" and not math.isnan(value):
            object.__setattr__(self, "launches", getattr(self, "launches", 0) + 1)
        object.__setattr__(self, key, value)


@pytest.mark.parametrize("load", (0.5, 0.9))
@pytest.mark.parametrize("name", ("megha", "sparrow", "eagle", "pigeon"))
def test_event_backend_launches_each_task_once_on_a_free_worker(
    monkeypatch, name, load
):
    """The event backend's invariants, without faults, at a light and a
    heavy load: every task launches exactly once, finishes at its launch
    time plus its duration, and no worker ever holds two tasks at once."""
    monkeypatch.setattr(base, "TaskRecord", _CountedLaunches)
    W = 128
    wl = synthetic_trace(num_jobs=30, tasks_per_job=32, load=load,
                         num_workers=W, seed=21)
    kw = dict(num_gms=4, num_lms=4) if name == "megha" else {}
    m = run_simulation(name, wl, num_workers=W, **kw)
    assert len(m.tasks) == wl.num_tasks
    assert len({(t.job_id, t.task_index) for t in m.tasks}) == wl.num_tasks
    by_worker = collections.defaultdict(list)
    for t in m.tasks:
        assert getattr(t, "launches", 0) == 1, (t.job_id, t.task_index)
        assert t.finish_time == t.start_time + t.duration, t
        assert 0 <= t.placed_worker < W, t
        by_worker[t.placed_worker].append((t.start_time, t.finish_time))
    for w, spans in by_worker.items():
        spans.sort()
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start >= end, (w, end, start)
