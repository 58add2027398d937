"""The benchmark's job traces, generated from a seed.

A configuration file's ``trace`` block names a generator and its
parameters; ``generate(trace, num_workers, seed)`` returns the trace as
flat numpy arrays in submit order (the layout the simulator's
``TaskArrays`` takes).  The two generators are copies of the program's
``repro.workload.synth.synthetic_trace`` and ``_trace_like`` (the paper's
synthetic Fig. 2 trace and its Table 1 trace surrogates), kept here so
that no change to the program can change the benchmark's traffic.  They
use Python's ``random.Random(seed)``, which takes any integer seed.
"""

from __future__ import annotations

import random

import numpy as np

#: _trace_like's short/long mixture (Delgado et al., Eagle): ~10% long jobs.
LONG_JOB_FRACTION = 0.10
SHORT_MEAN = 0.5   # seconds
LONG_MEAN = 45.0   # seconds


def _pareto(rng: random.Random, mean: float, alpha: float = 1.8) -> float:
    xm = mean * (alpha - 1.0) / alpha
    return min(xm * (1.0 - rng.random()) ** (-1.0 / alpha), mean * 50.0)


def synthetic(num_jobs, tasks_per_job, task_duration, load, num_workers,
              seed, arrivals="poisson"):
    """Jobs of ``tasks_per_job`` equal tasks, mean inter-arrival time set so
    that demand over capacity is ``load`` (the paper's Eq. 6).  Returns
    ``(job submit times, per-job duration lists)``."""
    if not 0.0 < load <= 1.0:
        raise ValueError(f"load {load} outside (0, 1]")
    rng = random.Random(seed)
    iat = tasks_per_job * task_duration / (load * num_workers)
    submit, durs, t = [], [], 0.0
    for _ in range(num_jobs):
        submit.append(t)
        durs.append([task_duration] * tasks_per_job)
        t += iat if arrivals == "fixed" else rng.expovariate(1.0 / iat)
    return submit, durs


def trace_like(num_jobs, total_tasks, load, num_workers, seed,
               long_fraction=LONG_JOB_FRACTION):
    """Table 1 trace surrogate: geometric task counts with the published
    mean, a short/long Pareto duration mixture, Poisson arrivals at the
    rate that gives ``load`` over the run, job order shuffled against
    size."""
    rng = random.Random(seed)
    mean_tasks = total_tasks / num_jobs
    counts, remaining = [], total_tasks
    for i in range(num_jobs):
        left = num_jobs - i
        if left == 1:
            c = max(1, remaining)
        else:
            c = max(1, min(int(rng.expovariate(1.0 / mean_tasks)) + 1,
                           remaining - (left - 1)))
        counts.append(c)
        remaining -= c
    durs, demand = [], 0.0
    for c in counts:
        mean = LONG_MEAN if rng.random() < long_fraction else SHORT_MEAN
        d = [max(0.05, _pareto(rng, mean)) for _ in range(c)]
        durs.append(d)
        demand += sum(d)
    span = demand / (load * num_workers)
    lam = num_jobs / span
    submit = [0.0] * num_jobs
    order = list(range(num_jobs))
    rng.shuffle(order)
    t = 0.0
    for idx in order:
        submit[idx] = t
        t += rng.expovariate(lam)
    by_time = sorted(range(num_jobs), key=lambda j: (submit[j], j))
    return [submit[j] for j in by_time], [durs[j] for j in by_time]


GENERATORS = {"synthetic": synthetic, "trace_like": trace_like}


def worker_labels(seed: int, num_workers: int, num_gms: int,
                  num_lms: int) -> np.ndarray:
    """int32[W]: a relabelling of megha's workers drawn from ``seed``.

    Each worker moves to another place in its own LM's partition of its
    own GM (``W / L / G`` consecutive workers), so it keeps its LM and
    its owner.  Workers are alike and start idle, so the GM priority
    orders relabelled by it schedule the same tasks in the same rounds
    onto other workers: every seed does the same work, and the state
    compared differs from seed to seed."""
    block = num_workers // num_lms // num_gms
    rng = random.Random(seed)
    labels = np.arange(num_workers, dtype=np.int32)
    for start in range(0, block * num_lms * num_gms, block):
        perm = list(range(start, start + block))
        rng.shuffle(perm)
        labels[start:start + block] = perm
    return labels


def to_arrays(submit, durs) -> dict[str, np.ndarray]:
    """Flatten jobs (already in submit order) to the per-task and per-job
    arrays: ``job``, ``duration``, ``submit`` over tasks; ``job_submit``,
    ``job_ideal`` (the longest task, Eq. 2's IdealJCT), ``job_ntasks`` and
    ``job_est`` (the estimate the long/short rules read: the ideal) over
    jobs."""
    ntasks = np.asarray([len(d) for d in durs], np.int32)
    job = np.repeat(np.arange(len(durs), dtype=np.int32), ntasks)
    duration = np.concatenate([np.asarray(d, np.float32) for d in durs])
    job_submit = np.asarray(submit, np.float32)
    job_ideal = np.asarray([max(d) for d in durs], np.float32)
    return dict(job=job, duration=duration, submit=job_submit[job],
                job_submit=job_submit, job_ideal=job_ideal,
                job_ntasks=ntasks, job_est=job_ideal.copy())


def generate(trace: dict, num_workers: int, seed: int) -> dict[str, np.ndarray]:
    """The trace a configuration's ``trace`` block describes, for ``seed``;
    a block that states its own ``seed`` gives one trace for every run.
    With ``shuffle_within_jobs`` as well, ``seed`` shuffles the durations
    of each job's tasks: every seed has the same jobs, arrivals, task
    counts and durations, and so the same task layout, each job's tasks
    in another order."""
    params = {k: v for k, v in trace.items()
              if k not in ("generator", "seed", "shuffle_within_jobs")}
    submit, durs = GENERATORS[trace["generator"]](
        num_workers=num_workers, seed=trace.get("seed", seed), **params
    )
    if trace.get("shuffle_within_jobs"):
        rng = random.Random(seed)
        for d in durs:
            rng.shuffle(d)
    return to_arrays(submit, durs)
