"""The numbers that decide ``correct``: the guarantees a configuration
states, checked task by task in numpy, and the comparison with each rule's
plain re-simulation (``ref_megha.py``, ``ref_sparrow.py``,
``ref_pigeon.py``).

None of it imports anything of the simulator.  It takes the trace the benchmark
generated (``tracegen``), the configuration's stated cluster and clock,
and the arrays the timed path produced, and recomputes from first
principles what every sound schedule of that trace must satisfy:

``clock_gap``
    The round clock: after ``R`` rounds the state reads ``rnd == R`` and
    ``t`` equal to ``R`` float32 additions of the stated ``dt`` from 0.
    Read as ``|rnd - R| + |t - clock[R]| / dt``.
``ledger_gap``
    The conservation ledger, joined from both sides: the tasks running by
    their own record (launched, finish after ``t``) are exactly the tasks
    the busy workers hold, one each, with equal finish times; nothing is
    lost (no faults are injected).  Read as the number of tasks or
    workers that break it, plus ``lost``.
``timing_errors``
    Every launched task's finish time is one the stated clock produces:
    ``float32(float32(clock[r] + hops * hop) + duration)`` for a round
    ``r`` before the state's round, at or after the task's submission
    (``submit <= clock[r]``).  Read as the number of tasks for which no
    such round exists.
``capacity_excess``
    At no instant do more tasks run than the cluster has workers, each
    task occupying ``[start, finish)``.  Read as the largest excess.

``ref_state_gap``
    The program's state after the window's first ``reference_rounds``
    rounds against the rule's re-simulation after as many: every task's
    finish time, every worker's finish time and task, the message and
    inconsistency counts (``state_gap``).

Each is exact, so each limit is 0.
"""

from __future__ import annotations

import numpy as np

#: every number the reference reads, with its limit
LIMITS = {"clock_gap": 0.0, "ledger_gap": 0.0, "timing_errors": 0.0,
          "capacity_excess": 0.0, "ref_state_gap": 0.0}


def round_clock(dt: float, rounds: int) -> np.ndarray:
    """float32[rounds + 1]: the round start times, ``t[r+1] = t[r] + dt``
    in float32 from ``t[0] = 0``."""
    step = np.float32(dt)
    out = np.empty(rounds + 1, np.float32)
    t = np.float32(0.0)
    for r in range(rounds + 1):
        out[r] = t
        t = np.float32(t + step)
    return out


def launch_rounds(fin, dur, submit, clock, rounds, dt, hop_s):
    """int[n]: for each launched task, the round whose launch gives exactly
    its finish time, at or after its submission; -1 where none does."""
    hop = np.float32(hop_s)
    est = np.rint((fin.astype(np.float64) - dur - hop_s) / dt).astype(np.int64)
    found = np.full(fin.shape, -1, np.int64)
    for off in (0, -1, 1, -2, 2):
        r = est + off
        ok = (r >= 0) & (r < rounds)
        rc = np.clip(r, 0, max(rounds - 1, 0))
        c = clock[rc]
        want = np.float32(c + hop) + dur
        hit = ok & (found < 0) & (want.astype(np.float32) == fin) & (submit <= c)
        found = np.where(hit, r, found)
    return found


def check_state(state: dict, trace: dict, *, rounds: int, dt: float,
                hop: float, hops: int, num_workers: int) -> dict[str, float]:
    """The four numbers for one simulated datacenter after ``rounds``
    rounds from a fresh start.  ``state`` holds numpy ``t``, ``rnd``,
    ``task_finish``, ``worker_finish``, ``worker_task``, ``lost``;
    ``trace`` the generated arrays."""
    clock = round_clock(dt, rounds)
    t = np.float32(state["t"])
    rnd = int(state["rnd"])
    clock_gap = abs(rnd - rounds) + abs(float(t) - float(clock[rounds])) / dt

    tf = np.asarray(state["task_finish"], np.float32)
    wf = np.asarray(state["worker_finish"], np.float32)
    wt = np.asarray(state["worker_task"], np.int64)
    T = tf.shape[0]
    running = np.isfinite(tf) & (tf > t)
    busy = wf > t
    held = wt[busy]
    valid = (held >= 0) & (held < T)
    held_ok = held[valid]
    match = np.zeros(held.shape, bool)
    match[valid] = running[held_ok] & (tf[held_ok] == wf[busy][valid])
    dup = held_ok.size - np.unique(held_ok).size
    ledger_gap = (int((~match).sum()) + dup
                  + abs(int(running.sum()) - int(busy.sum()))
                  + int(state["lost"]))

    dur = np.asarray(trace["duration"], np.float32)
    submit = np.asarray(trace["submit"], np.float32)
    launched = np.isfinite(tf)
    hop_s = hops * hop
    r = launch_rounds(tf[launched], dur[launched], submit[launched], clock,
                      rounds, dt, hop_s)
    timing_errors = int((r < 0).sum())

    start = np.float32(clock[np.clip(r, 0, rounds)] + np.float32(hop_s))
    start = start[r >= 0]
    fin = tf[launched][r >= 0]
    s_sorted, f_sorted = np.sort(start), np.sort(fin)
    running_at = (np.searchsorted(s_sorted, s_sorted, side="right")
                  - np.searchsorted(f_sorted, s_sorted, side="right"))
    capacity_excess = max(0, int(running_at.max(initial=0)) - num_workers)
    return {"clock_gap": float(clock_gap), "ledger_gap": float(ledger_gap),
            "timing_errors": float(timing_errors),
            "capacity_excess": float(capacity_excess)}


def state_gap(got: dict, ref: dict) -> float:
    """Tasks and workers whose record differs between the program's state
    and the reference's after the same rounds (task finish times; each
    worker's finish time and task), plus the differences of the message
    and inconsistency counts."""
    return float(
        (np.asarray(got["task_finish"]) != ref["task_finish"]).sum()
        + (np.asarray(got["worker_finish"]) != ref["worker_finish"]).sum()
        + (np.asarray(got["worker_task"]) != ref["worker_task"]).sum()
        + abs(int(got["messages"]) - ref["messages"])
        + abs(int(got["inconsistencies"]) - ref["inconsistencies"]))


def arrived(trace: dict, t: float) -> int:
    """Tasks submitted by simulated time ``t``."""
    return int((np.asarray(trace["submit"], np.float32) <= np.float32(t)).sum())



def done(state: dict, trace: dict) -> dict[str, int]:
    """Tasks and jobs finished by the state's time."""
    fin = np.asarray(state["task_finish"]) <= state["t"]
    left = np.bincount(np.asarray(trace["job"]), weights=~fin,
                       minlength=np.asarray(trace["job_ntasks"]).size)
    return {"tasks_done": int(fin.sum()), "jobs_done": int((left == 0).sum())}
