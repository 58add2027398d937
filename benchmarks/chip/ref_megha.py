"""A plain numpy simulation of megha's rounds, the reference for the megha
cells.

It follows the round-synchronous megha semantics the simulator documents
(``docs/simx_runtime.md``; arXiv:2308.10178 Sec. 3), fault-free, one round
at a time, in float32 where the simulator keeps time:

1. complete: a worker is free iff its finish time has passed; a task
   finished in the round just ended returns a non-borrowed worker to its
   GM's view at once (one LM -> GM message each);
2. heartbeat: every ``heartbeat / dt`` rounds every GM view becomes the
   truth (G x L messages);
3. internal match: each GM proposes its queued tasks, in FIFO order over a
   window of ``max(W / G, 64)`` tasks past its launched prefix, onto the
   free workers of its own partitions as its view shows them, in its
   priority order; an LM launches a proposal iff the worker really is
   free, and a rejected GM gets that LM's truth (an inconsistency);
4. borrow match, in rounds where some GM has more queued tasks than it
   proposed: every GM proposes over its whole priority order, a per-round
   rotating GM priority settles a worker claimed twice, and the LMs
   verify as before.

A launch starts three hops after the round's time and ends ``duration``
later.  Tasks go to GM ``job % G``.  The per-GM priority orders are the
seeded permutations the paper's GMs draw (own partitions first), made
with ``jax.random`` from the scheduler seed on the host CPU.  It imports
nothing of the simulator.
"""

from __future__ import annotations

import numpy as np


def priority_orders(seed: int, W: int, G: int, L: int) -> np.ndarray:
    """int[G, W]: GM g's priority order over workers, its own partitions
    shuffled first, then the rest shuffled, from ``fold_in(key(seed), g)``
    split in two."""
    import jax

    cpu = jax.devices("cpu")[0]
    wpl = W // L
    part_gm = (np.arange(W) % wpl) // (wpl // G)
    rows = []
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(seed)
        for g in range(G):
            k_int, k_ext = jax.random.split(jax.random.fold_in(key, g))
            own = np.nonzero(part_gm == g)[0].astype(np.int32)
            other = np.nonzero(part_gm != g)[0].astype(np.int32)
            rows.append(np.concatenate([
                np.asarray(jax.random.permutation(k_int, own)),
                np.asarray(jax.random.permutation(k_ext, other)),
            ]))
    return np.stack(rows).astype(np.int32)


def _match(avail: np.ndarray, n: np.ndarray) -> np.ndarray:
    """Rank-and-select: the r-th available worker in a row takes the r-th
    of its ``n`` tasks; -1 elsewhere."""
    rank = np.cumsum(avail, axis=1) - 1
    return np.where(avail & (rank < n[:, None]), rank, -1)


def simulate(trace: dict, cluster: dict, *, seed: int, rounds: int,
             snapshots=(), labels=None) -> dict[int, dict]:
    """Run ``rounds`` rounds of the stated ``cluster`` from idle, the GM
    orders drawn from scheduler seed ``seed`` and, with ``labels``, each
    worker ``w`` in them renamed ``labels[w]``; returns the state after
    each round count in ``snapshots`` and after ``rounds``."""
    W, G, L = cluster["num_workers"], cluster["num_gms"], cluster["num_lms"]
    heartbeat_interval, hop = cluster["heartbeat_interval"], cluster["hop"]
    dt = cluster["dt"]
    wpl = W // L
    part_gm = (np.arange(W) % wpl) // (wpl // G)
    orders = priority_orders(seed, W, G, L)
    if labels is not None:
        orders = np.asarray(labels)[orders]
    wi = W // G
    own = orders[:, :wi]                      # [G, wi] own workers
    own_lm = own // wpl
    hb = max(1, int(round(heartbeat_interval / dt)))

    job = np.asarray(trace["job"])
    T = job.size
    dur = np.asarray(trace["duration"], np.float32)
    submit = np.asarray(trace["submit"], np.float32)
    task_gm = job % G
    tg = max(1, int(np.bincount(task_gm, minlength=G).max()))
    C = min(max(W // G, 64), tg)
    gm_tasks = np.full((G, tg + C), T, np.int64)
    for g in range(G):
        mine = np.nonzero(task_gm == g)[0]
        gm_tasks[g, :mine.size] = mine
    submit_pad = np.append(submit, np.float32(np.inf))
    dur_pad = np.append(dur, np.float32(0.0))
    g_col = np.arange(G)[:, None]
    cols = np.arange(C)
    step, hop3 = np.float32(dt), np.float32(3 * hop)

    t = np.float32(0.0)
    # task_finish, with a launched pad entry at T for the window's padding
    finish_pad = np.full(T + 1, np.inf, np.float32)
    finish_pad[T] = -np.inf
    task_finish = finish_pad[:T]
    worker_finish = np.full(W, -np.inf, np.float32)
    worker_task = np.full(W, T, np.int64)
    worker_gm = np.zeros(W, np.int64)
    borrowed = np.zeros(W, bool)
    view = np.ones((G, W), bool)
    head = np.zeros(G, np.int64)
    msgs = incons = 0
    work = {"heartbeats": 0, "borrow_rounds": 0}

    def launch(mask, task, gm, t):
        start = np.float32(t + hop3)
        fin = (start + dur_pad[task[mask]]).astype(np.float32)
        task_finish[task[mask]] = fin
        worker_finish[mask] = fin
        worker_task[mask] = task[mask]
        worker_gm[mask] = gm[mask]
        borrowed[mask] = part_gm[mask] != gm[mask]

    def window():
        wtask = gm_tasks[g_col, head[:, None] + cols]            # [G, C]
        wsub = submit_pad[wtask]
        launched = np.isfinite(finish_pad[wtask])
        queued = ~launched & (wsub <= t)
        fifo = np.sort(np.where(queued, cols, C), axis=1)
        return wtask, launched, queued, fifo

    def pick(ranks, fifo, wtask):
        pos = np.take_along_axis(fifo, np.clip(ranks, 0, C - 1), axis=1)
        sel = np.take_along_axis(wtask, np.clip(pos, 0, C - 1), axis=1)
        return np.where(ranks >= 0, sel, -1)

    out = {}
    want = set(snapshots) | {rounds}
    quiet = False
    for rnd in range(rounds):
        if rnd in want:
            out[rnd] = _snap(t, rnd, task_finish, worker_finish, worker_task,
                             msgs, incons, work)
        # every task launched and every worker idle since before this
        # round: nothing but the clock and the heartbeats changes again
        quiet = quiet or (bool(np.isfinite(task_finish).all())
                          and bool((worker_finish <= np.float32(t - step)).all()))
        if quiet:
            if rnd % hb == hb - 1:
                msgs += G * L
            t = np.float32(t + step)
            continue
        truth = worker_finish <= t
        comp = truth & (worker_finish > np.float32(t - step))
        view |= (worker_gm[None, :] == g_col) & (comp & ~borrowed)[None, :]
        msgs += int(comp.sum())
        if rnd % hb == hb - 1:
            view[:] = truth[None, :]
            msgs += G * L
            work["heartbeats"] += 1

        wtask, _, queued, fifo = window()
        nq = queued.sum(axis=1)
        sel = pick(_match(view[g_col, own], nq), fifo, wtask)  # [G, wi]
        proposed = sel >= 0
        ok = proposed & truth[own]
        bad = proposed & ~truth[own]
        lw = np.zeros(W, bool)
        lw[own[ok]] = True
        task_w = np.full(W, T, np.int64)
        task_w[own[ok]] = sel[ok]
        launch(lw, task_w, part_gm, t)
        truth &= ~lw
        mine = np.zeros(W, bool)
        mine[own[proposed]] = True
        view &= ~(mine[None, :] & (part_gm[None, :] == g_col))
        incons += int(bad.sum())
        for g in range(G):
            for lm in np.unique(own_lm[g][bad[g]]):
                view[g, lm * wpl:(lm + 1) * wpl] = truth[lm * wpl:(lm + 1) * wpl]
            msgs += 2 * np.unique(own_lm[g][proposed[g]]).size

        if np.any(nq > proposed.sum(axis=1)):
            work["borrow_rounds"] += 1
            wtask, _, queued, fifo = window()
            avail = np.take_along_axis(view, orders, axis=1)
            sel_o = pick(_match(avail, queued.sum(axis=1)), fifo, wtask)
            prop = np.full((G, W), -1, np.int64)
            np.put_along_axis(prop, orders, sel_o, axis=1)
            proposed = prop >= 0
            prio = np.where(proposed, ((g_col + rnd) % G) * G + g_col, G * G)
            win = prio.min(axis=0)
            claimed = win < G * G
            win_g = np.where(claimed, win % G, 0)
            lw = claimed & truth
            task_w = np.where(lw, prop[win_g, np.arange(W)], T)
            launch(lw, task_w, win_g, t)
            truth &= ~lw
            view &= ~proposed
            bad = proposed & ~(lw[None, :] & (win_g[None, :] == g_col))
            incons += int(bad.sum())
            bad_gl = bad.reshape(G, L, wpl).any(axis=2)
            refresh = np.repeat(bad_gl, wpl, axis=1)
            view = np.where(refresh, truth[None, :], view)
            msgs += 2 * int(proposed.reshape(G, L, wpl).any(axis=2).sum())

        _, launched, _, _ = window()
        lead = np.cumprod(launched, axis=1).sum(axis=1)
        head = np.minimum(head + lead, tg)
        t = np.float32(t + step)
    out[rounds] = _snap(t, rounds, task_finish, worker_finish, worker_task,
                        msgs, incons, work)
    return out


def _snap(t, rnd, task_finish, worker_finish, worker_task, msgs, incons,
          work):
    return {"t": np.float32(t), "rnd": rnd, "task_finish": task_finish.copy(),
            "worker_finish": worker_finish.copy(),
            "worker_task": worker_task.copy(), "messages": msgs,
            "inconsistencies": incons, "lost": 0, "work": dict(work)}

