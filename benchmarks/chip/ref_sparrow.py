"""A plain numpy simulation of sparrow's rounds, the reference for the
sparrow cell.

It follows the round-synchronous sparrow semantics the simulator documents
(arXiv:2308.10178 Sec. 2.2; ``simx/engine.py``'s approximation contract),
fault-free, one round at a time, in float32 where the simulator keeps time:

* probes: job ``j`` probes ``min(d * n_j, W)`` distinct workers, its row of
  the seeded score table in descending order (jobs in chunks of
  ``2**21 // W`` rows, chunk ``i`` scored by ``uniform(fold_in(key, i))``,
  made with ``jax.random`` on the host CPU);
* each worker keeps a queue of ``R`` reservation slots, ``R`` twice the
  average probes a worker gets over the trace, between 8 and 64; probes
  arrive through a window of ``C`` edges of the arrival-ordered probe list
  a round (at least 256, four of the largest jobs' probes and a 32nd of
  the list), only edges whose job has been submitted; a probe that finds
  its worker's queue full is dropped;
* each round first drops the entries of jobs with no unfinished task and
  closes the gaps, then appends the window's probes in edge order, a
  probe of a job its worker already queues merging into that entry;
* an idle worker serves the earliest queued job that still has a pending
  task; a job with pending work and no entry left anywhere, all of whose
  probes have been sent, is served by any idle worker (the lowest such
  job, where it comes before the worker's own);
* late binding: the k-th idle worker (by index) serving a job gets its
  k-th pending task (by index); a launch starts three hops after the
  round's time; each probe is one message, each launch two.
"""

from __future__ import annotations

import math

import numpy as np


def top(scores: np.ndarray, k: int) -> np.ndarray:
    """Indices of the ``k`` largest scores, largest first, the lower index
    first among equal scores."""
    kth = -np.partition(-scores, k - 1)[k - 1]
    above = np.nonzero(scores > kth)[0]
    tied = np.nonzero(scores == kth)[0][:k - above.size]
    idx = np.concatenate([above, tied])
    return idx[np.lexsort((idx, -scores[idx]))]


def probe_table(seed: int, ntasks: np.ndarray, W: int, d: int) -> np.ndarray:
    """int[J, kmax]: each job's probe targets in descending score order."""
    import jax

    J = ntasks.size
    kmax = int(min(d * int(ntasks.max(initial=0)), W))
    chunk = int(max(1, min(J, (1 << 21) // max(W, 1))))
    rows = []
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.PRNGKey(seed)
        for i in range(-(-J // chunk)):
            scores = np.asarray(jax.random.uniform(jax.random.fold_in(key, i),
                                                   (chunk, W)))
            rows.extend(top(row, kmax) for row in scores)
    return np.stack(rows[:J])


def simulate(trace: dict, cluster: dict, *, seed: int,
             rounds: int) -> dict[int, dict]:
    """Run ``rounds`` rounds of the stated ``cluster`` from idle, the probes
    drawn from scheduler seed ``seed``; returns ``{rounds: state}``."""
    W, d = cluster["num_workers"], cluster["probe_ratio"]
    hop, dt = cluster["hop"], cluster["dt"]
    job = np.asarray(trace["job"])
    T = job.size
    ntasks = np.asarray(trace["job_ntasks"])
    J = ntasks.size
    k = np.minimum(d * ntasks.astype(np.int64), W)
    table = probe_table(seed, ntasks, W, d)
    edge_job = np.repeat(np.arange(J), k)
    edge_worker = np.concatenate([table[j, :k[j]] for j in range(J)])
    edge_end = np.cumsum(k)
    P = edge_job.size
    R = min(max(8, 2 * math.ceil(P / W)), 64)
    C = min(P, max(256, 4 * int(k.max()), math.ceil(P / 32)))
    job_start = np.concatenate([[0], np.cumsum(ntasks)[:-1]])
    job_submit = np.asarray(trace["job_submit"], np.float32)
    submit = np.asarray(trace["submit"], np.float32)
    dur = np.asarray(trace["duration"], np.float32)
    step, hop3 = np.float32(dt), np.float32(3 * hop)

    # every task of a job arrives with it
    assert (submit == job_submit[job]).all()

    t = np.float32(0.0)
    task_finish = np.full(T, np.inf, np.float32)
    worker_finish = np.full(W, -np.inf, np.float32)
    worker_task = np.full(W, T, np.int64)
    queue = np.full((W, R), J, np.int32)
    width = 1            # queues are packed left: columns past this are empty
    done = np.zeros(J, np.int64)        # tasks finished by t, per job
    launched = np.zeros(J, np.int64)    # tasks launched, per job
    run_fin = np.zeros(0, np.float32)   # running tasks not yet counted done
    run_job = np.zeros(0, np.int64)
    head = msgs = overflow = compacted = orphan_rounds = 0
    for _ in range(rounds):
        # drop entries of finished jobs, close the gaps
        now = run_fin <= t
        done += np.bincount(run_job[now], minlength=J)
        run_fin, run_job = run_fin[~now], run_job[~now]
        q = queue[:, :width]
        occupied = q < J
        live = occupied & (np.append(ntasks - done, 0)[q] > 0)
        fill = live.sum(axis=1)
        dropped = int(occupied.sum()) - int(fill.sum())
        compacted += dropped
        if dropped:
            packed = np.full((W, width), J, np.int32)
            packed[np.nonzero(live)[0],
                   (np.cumsum(live, axis=1) - 1)[live]] = q[live]
            queue[:, :width] = packed

        # insert the window's submitted prefix of probes, in edge order
        win = np.arange(head, min(head + C, P))
        ready = job_submit[edge_job[win]] <= t
        lead = int(np.cumprod(ready).sum())
        w, j = edge_worker[head:head + lead], edge_job[head:head + lead]
        pair = w * (J + 1) + j
        first = np.zeros(lead, bool)
        first[np.unique(pair, return_index=True)[1]] = True
        keep = first & ~(queue[w, :width] == j[:, None]).any(axis=1)
        w, j = w[keep], j[keep]
        order = np.argsort(w, kind="stable")
        rank = np.empty(w.size, np.int64)
        rank[order] = np.arange(w.size) - np.searchsorted(w[order], w[order])
        slot = fill[w] + rank
        fits = slot < R
        queue[w[fits], slot[fits]] = j[fits]
        width = max(width, int(slot[fits].max(initial=-1)) + 1)
        overflow += int((~fits).sum())
        head += lead
        msgs += lead

        # each idle worker's pick: its earliest queued job with pending work
        q = queue[:, :width]
        pending = np.where(job_submit <= t, ntasks, 0) - launched
        active = (q < J) & (np.append(pending, 0)[q] > 0)
        first = active.argmax(axis=1)
        pick = np.where(active.any(axis=1), q[np.arange(W), first], J)
        held = np.zeros(J + 1, bool)
        held[q.ravel()] = True
        orphan = (edge_end <= head) & (pending > 0) & ~held[:J]
        if orphan.any():
            orphan_rounds += 1
            pick = np.minimum(pick, np.argmax(orphan))
        pick = np.where(worker_finish <= t, pick, J)

        # late binding, worker order against task order within each job
        idle = np.nonzero(pick < J)[0]
        idle = idle[np.argsort(pick[idle], kind="stable")]
        jobs, at = np.unique(pick[idle], return_index=True)
        for j, ws in zip(jobs, np.split(idle, at[1:])):
            a = job_start[j]
            ts = a + np.nonzero(np.isinf(task_finish[a:a + ntasks[j]]))[0]
            n = min(ws.size, ts.size)
            fin = (np.float32(t + hop3) + dur[ts[:n]]).astype(np.float32)
            task_finish[ts[:n]] = fin
            worker_finish[ws[:n]] = fin
            worker_task[ws[:n]] = ts[:n]
            launched[j] += n
            run_fin = np.concatenate([run_fin, fin])
            run_job = np.concatenate([run_job, np.full(n, j)])
            msgs += 2 * n
        t = np.float32(t + step)
    return {rounds: {"t": t, "rnd": rounds, "task_finish": task_finish,
                     "worker_finish": worker_finish,
                     "worker_task": worker_task, "messages": msgs,
                     "inconsistencies": 0, "lost": 0,
                     "work": {"compacted": compacted,
                              "orphan_rounds": orphan_rounds,
                              "probes_dropped": overflow}}}
