"""A plain numpy simulation of pigeon's rounds, the reference for the
pigeon cell.

It follows the round-synchronous pigeon semantics the simulator documents
(arXiv:2308.10178 Sec. 2.2.4; ``docs/simx_runtime.md``), fault-free, one
round at a time, in float32 where the simulator keeps time:

* distribution: jobs go round-robin over the distributors; each
  distributor deals its jobs' tasks round-robin over the worker groups,
  its counter persisting from job to job (distributor ``d`` starts at
  group ``d``);
* each group keeps a high-priority FIFO (jobs whose estimate is under the
  long threshold) and a low-priority one, in task order, and launches
  strictly from their heads;
* a group's first ``reserved_per_group`` workers run high-priority tasks
  only; the other free workers are shared by weighted fair queuing, one
  low task per ``wfq_weight`` high ones, the count of highs since the
  last low carried from round to round; highs that find no shared worker
  overflow onto free reserved ones;
* a task a group launches starts three hops after the round's time; each
  arrival and each launch is one message.
"""

from __future__ import annotations

import numpy as np


def _match(avail: np.ndarray, n: np.ndarray) -> np.ndarray:
    rank = np.cumsum(avail, axis=1) - 1
    return np.where(avail & (rank < n[:, None]), rank, -1)


def simulate(trace: dict, cluster: dict, *, seed: int,
             rounds: int) -> dict[int, dict]:
    """Run ``rounds`` rounds of the stated ``cluster`` from idle; returns
    ``{rounds: state}``.  Pigeon draws nothing at random: ``seed`` is
    unused."""
    del seed
    W, D = cluster["num_workers"], cluster["num_distributors"]
    wt, group_size = cluster["wfq_weight"], cluster["group_size"]
    reserved_per_group = cluster["reserved_per_group"]
    long_threshold = cluster["long_threshold"]
    hop, dt = cluster["hop"], cluster["dt"]
    NG = max(1, W // group_size)
    sizes = np.full(NG, group_size)
    sizes[-1] = W - (NG - 1) * group_size
    S = int(sizes.max())
    wg = np.full((NG, S), W, np.int64)
    reserved = np.zeros((NG, S), bool)
    for g in range(NG):
        wg[g, :sizes[g]] = g * group_size + np.arange(sizes[g])
        reserved[g, :min(reserved_per_group, sizes[g])] = True

    job = np.asarray(trace["job"])
    T = job.size
    ntasks = np.asarray(trace["job_ntasks"])
    group = np.empty(T, np.int64)
    rr = np.arange(D)
    k = 0
    for p, c in enumerate(ntasks):
        group[k:k + c] = (rr[p % D] + np.arange(c)) % NG
        rr[p % D] += c
        k += c
    high = np.asarray(trace["job_est"], np.float32)[job] < np.float32(
        long_threshold)

    def fifo(mask):
        n = int(np.bincount(group[mask], minlength=NG).max()) if mask.any() else 0
        rows = np.full((NG, n + S), T, np.int64)
        for g in range(NG):
            mine = np.nonzero(mask & (group == g))[0]
            rows[g, :mine.size] = mine
        return rows, n

    hi_fifo, len_h = fifo(high)
    lo_fifo, len_l = fifo(~high)
    submit = np.asarray(trace["submit"], np.float32)
    assert (np.diff(submit) >= 0).all(), "tasks in submit order"
    submit_pad = np.append(submit, np.float32(np.inf))
    dur_pad = np.append(np.asarray(trace["duration"], np.float32),
                        np.float32(0.0))
    cols = np.arange(S)
    step, hop3 = np.float32(dt), np.float32(3 * hop)

    t = np.float32(0.0)
    task_finish = np.full(T, np.inf, np.float32)
    worker_finish = np.full(W, -np.inf, np.float32)
    worker_task = np.full(W, T, np.int64)
    hi_head = np.zeros(NG, np.int64)
    lo_head = np.zeros(NG, np.int64)
    since_low = np.zeros(NG, np.int64)
    msgs = 0
    work = {"wfq_contended": 0, "low_launches": 0, "reserved_launches": 0}
    rows = np.arange(NG)[:, None]
    for _ in range(rounds):
        free = np.append(worker_finish <= t, False)[wg]
        free_u, free_r = free & ~reserved, free & reserved
        nfu, nfr = free_u.sum(axis=1), free_r.sum(axis=1)
        wh = hi_fifo[rows, hi_head[:, None] + cols]
        wl = lo_fifo[rows, lo_head[:, None] + cols]
        qh = (submit_pad[wh] <= t).sum(axis=1)
        ql = (submit_pad[wl] <= t).sum(axis=1)

        total = np.minimum(nfu, qh + ql)
        lead = np.maximum(0, wt - since_low)
        n_low = np.where(total > lead, 1 + (total - lead - 1) // (wt + 1), 0)
        n_low = np.minimum(np.maximum(n_low, np.maximum(total - qh, 0)),
                           np.minimum(ql, total))
        n_high = total - n_low
        n_res = np.minimum(qh - n_high, nfr)
        since_low = np.maximum(0, since_low + n_high - wt * n_low)
        work["wfq_contended"] += int(((qh > 0) & (ql > 0)
                                      & (nfu < qh + ql)).sum())
        work["low_launches"] += int(n_low.sum())
        work["reserved_launches"] += int(np.maximum(n_res, 0).sum())

        ru = _match(free_u, total)
        rr_ = _match(free_r, n_res)
        pick_h = np.take_along_axis(wh, np.clip(ru, 0, S - 1), axis=1)
        pick_l = np.take_along_axis(
            wl, np.clip(ru - n_high[:, None], 0, S - 1), axis=1)
        task_u = np.where(ru < 0, T,
                          np.where(ru < n_high[:, None], pick_h, pick_l))
        task_r = np.where(rr_ < 0, T, np.take_along_axis(
            wh, np.clip(n_high[:, None] + rr_, 0, S - 1), axis=1))
        task = np.minimum(task_u, task_r)
        go = task < T
        fin = (np.float32(t + hop3) + dur_pad[task[go]]).astype(np.float32)
        task_finish[task[go]] = fin
        worker_finish[wg[go]] = fin
        worker_task[wg[go]] = task[go]
        msgs += int(np.searchsorted(submit, t, "right")
                    - np.searchsorted(submit, np.float32(t - step), "right"))
        msgs += int(go.sum())
        hi_head = np.minimum(hi_head + n_high + n_res, len_h)
        lo_head = np.minimum(lo_head + n_low, len_l)
        t = np.float32(t + step)
    return {rounds: {"t": t, "rnd": rounds, "task_finish": task_finish,
                     "worker_finish": worker_finish,
                     "worker_task": worker_task, "messages": msgs,
                     "inconsistencies": 0, "lost": 0, "work": work}}
