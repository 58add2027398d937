"""Megha transition rule for the simx round-stepped backend.

One round advances the whole datacenter by ``cfg.dt`` simulated seconds:

  1. **complete** — workers whose task finished inside the round window just
     ended free up; the scheduling GM's view regains NON-borrowed workers
     immediately (borrowed ones wait for the owner's heartbeat, §3.4).
  2. **heartbeat** — every ``heartbeat_rounds`` rounds all LM snapshots
     overwrite every GM view (§3.1).  Round-synchronous execution means no
     placement is in flight at this point, so the full overwrite is exact.
  3. **internal match** — each GM ranks the free workers of its own
     partitions (per its GM-specific shuffled priority order, §3.3) with the
     rank-and-select primitive and proposes its queued tasks (FIFO) onto
     them.  Internal partitions are disjoint across GMs, so no cross-GM
     arbitration is needed; the LM ground truth still verifies each mapping
     (a stale view can show a worker free that another GM borrowed).
  4. **borrow match** (``lax.cond``, only when some GM's queue exceeds its
     internal free view) — the full §3.2 repartition pass: every GM matches
     its remaining queue over its whole priority order (internal first,
     then external), simultaneous claims arbitrated by a per-round rotating
     GM priority, LM truth verifying.  Failed proposals in either phase are
     inconsistencies: the proposing GM keeps those workers marked busy and
     receives a piggybacked fresh snapshot of every LM that rejected it
     (§3.4.1); losing tasks stay queued (FIFO retry next round).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.simx import runtime as rt, spans
from repro.simx.faults import (
    FaultSchedule,
    gm_adoption,
    gm_down_mask,
    gm_recovered_now,
)
from repro.simx.runtime import (  # noqa: F401 — canonical home is runtime;
    MatchFn,                      # re-exported here for the existing call
    default_match_fn,             # sites (tests, benchmarks, engine)
)
from repro.simx.state import (
    MeghaState,
    SimxConfig,
    TaskArrays,
    init_megha_state,
    spec,
)


@spans.span("simx.build")
def gm_orders(key: jax.Array, cfg: SimxConfig) -> jax.Array:
    """int32[G, W] per-GM priority permutations: own partitions (shuffled)
    first, then external partitions (shuffled), mirroring
    ``GlobalManager.__init__`` / ``fastpath.make_orders``."""
    cfg.validate_megha_grid()
    w = np.arange(cfg.num_workers)
    part_gm = (w % cfg.workers_per_lm) // cfg.partition_size
    rows = []
    for g in range(cfg.num_gms):
        k_int, k_ext = jax.random.split(jax.random.fold_in(key, g))
        internal = jnp.asarray(w[part_gm == g], jnp.int32)
        external = jnp.asarray(w[part_gm != g], jnp.int32)
        rows.append(
            jnp.concatenate(
                [
                    jax.random.permutation(k_int, internal),
                    jax.random.permutation(k_ext, external),
                ]
            )
        )
    return jnp.stack(rows)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class MeghaLayout:
    """Traced per-window task layout for the streaming engine.

    The fixed path bakes the per-GM FIFO layout into the step as numpy
    closure constants; the streaming engine instead passes the layout as
    *traced* arrays so one compiled step serves every refilled window.
    ``gm_tasks`` rows list each GM's window-task ids in submit order
    (GM = global job id % G, so a carried job keeps its GM across
    refills), padded with the window sentinel ``T``; ``gm_len`` holds the
    real row lengths for the head clamp.  ``window`` is the static match
    window C the rows were padded for.
    """

    gm_tasks: jax.Array = spec("int32[G, ?]")  # rows: tg_cap + window
    gm_len: jax.Array = spec("int32[G]")
    window: int = dataclasses.field(metadata=dict(static=True))


@spans.span("simx.build")
def make_megha_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    orders: jax.Array,
    match_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
    layout: Optional[MeghaLayout] = None,
) -> Callable[[MeghaState], MeghaState]:
    """Build the jittable one-round transition function.

    Hot-loop layout notes (CPU XLA scatters are scalar loops, so the round
    is built from gathers, a small row sort, and elementwise ops — one
    [W]-wide scatter per phase, the task-finish write at launch):

      * tasks live in a compact per-GM layout ``gm_tasks[G, Tg]`` (static
        round-robin partition, padded with the OOB sentinel T);
      * each GM only examines a ``C``-wide FIFO *window* starting at its
        launched-prefix ``head`` pointer, so per-round cost is independent
        of the trace length.  Matches are therefore capped at C per GM per
        round; the auto window (``cfg.match_window == 0``) is
        ``C = max(W / G, 64)``, so the G GMs together can fill the whole DC
        in one round and the cap only binds under extreme borrow imbalance
        (where it just delays the surplus to the next round);
      * the common case runs entirely on [G, W/G] internal-partition
        arrays; the [G, W]-wide borrow pass is entered via ``lax.cond``
        only on rounds where a GM's queue outruns its internal free view;
      * GM->worker coordinate conversion goes through precomputed inverse
        permutations (gathers), never scatters.

    With ``faults`` (a ``repro.simx.faults.FaultSchedule``) the round gains
    the §3.5 masked fault transitions: crashed workers lose their in-flight
    task (re-pended, GM FIFO head rolled back) and read busy until their
    recovery time — stale views keep proposing onto them until heartbeats /
    piggybacks repair the inconsistency; down GMs stop matching and their
    queues are adopted round-robin by live GMs matching against their own
    views (arrival rerouting); a recovering GM's view resets from LM ground
    truth (``rebuild_from_heartbeats``).  ``faults=None`` builds exactly
    the fault-free program, and an *empty* schedule is bit-identical to it.
    """
    if match_fn is None:
        match_fn = default_match_fn()
    cfg.validate_megha_grid()
    G, L, W = cfg.num_gms, cfg.num_lms, cfg.num_workers
    wpl = cfg.workers_per_lm
    wi = W // G                                        # internal workers per GM
    T = tasks.num_tasks
    hb = cfg.heartbeat_rounds
    part_gm = cfg.partition_gms()                      # int32[W]
    g_col = jnp.arange(G, dtype=jnp.int32)[:, None]
    l_row = jnp.arange(L, dtype=jnp.int32)[None, None, :]
    w_row = jnp.arange(W, dtype=jnp.int32)
    inv_orders = jnp.argsort(orders, axis=1)           # int32[G,W]
    int_ord = orders[:, :wi]                           # int32[G,wi] own workers
    # rows of int_ord partition [0, W): flattening gives a W-permutation
    inv_int = jnp.argsort(int_ord.reshape(-1))         # int32[W] -> flat (g,i)
    lm_int = int_ord // wpl                            # int32[G,wi]
    if layout is None:
        # compact per-GM task partition (jobs round-robin over GMs)
        task_gm = np.asarray(tasks.job) % G
        tg = max(1, int(np.max(np.bincount(task_gm, minlength=G))))
        C = cfg.match_window or max(W // G, 64)
        C = min(C, tg)
        # pad with C sentinels so the head window never slices out of bounds
        gm_tasks_np = np.full((G, tg + C), T, np.int32)
        task_pos_np = np.zeros(T + 1, np.int32)        # task -> window position
        for g in range(G):
            mine = np.nonzero(task_gm == g)[0]
            gm_tasks_np[g, : mine.size] = mine
            task_pos_np[mine] = np.arange(mine.size, dtype=np.int32)
        gm_tasks = jnp.asarray(gm_tasks_np)            # int32[G,Tg+C]
        gm_len = tg
    else:
        if faults is not None:
            raise NotImplementedError(
                "streaming layout does not compose with fault schedules"
            )
        gm_tasks = layout.gm_tasks
        C = layout.window
        gm_len = layout.gm_len
    if faults is not None:
        # task -> (gm row, FIFO position) for crash-loss head rollback;
        # the T pad rows route to the out-of-bounds row G (scatter-dropped)
        task_gm_pad = jnp.concatenate(
            [jnp.asarray(task_gm, jnp.int32), jnp.int32([G])]
        )
        task_pos_pad = jnp.asarray(task_pos_np)
    # task submit times in the padded compact layout (sentinel -> inf)
    submit_c = jnp.concatenate([tasks.submit, jnp.float32([jnp.inf])])[gm_tasks]
    dur_pad = jnp.concatenate([tasks.duration, jnp.float32([0.0])])

    def launch_updates(t, launch_w, task_w, gm_w, task_finish, worker_finish,
                       worker_task, worker_gm, worker_borrowed):
        """Apply one phase's launches ([W]-space masks): the shared launch
        bookkeeping plus megha's owner/borrow tracking.  start = round
        time + client->GM + GM->LM + LM->worker hops."""
        task_finish, worker_finish, worker_task = rt.apply_launch(
            launch_w, task_w, t + 3 * cfg.hop, dur_pad,
            task_finish, worker_finish, worker_task, T,
        )
        worker_gm = jnp.where(launch_w, gm_w, worker_gm)
        worker_borrowed = jnp.where(launch_w, part_gm != gm_w, worker_borrowed)
        return task_finish, worker_finish, worker_task, worker_gm, worker_borrowed

    def piggyback(view, truth, invalid_gl, adopt=None):
        """Refresh GM g's view of every LM that rejected one of its
        proposals with that LM's fresh ground truth (§3.4.1).  Under GM
        adoption the refresh lands on the *adopter's* view (it made the
        proposal); ``adopt`` is the identity without down GMs, so the
        scatter reduces to the plain row-local refresh."""
        if adopt is not None:
            invalid_gl = jnp.zeros_like(invalid_gl).at[adopt].max(invalid_gl)
        refresh = jnp.repeat(invalid_gl, wpl, axis=1)             # bool[G,W]
        return jnp.where(refresh, truth[None, :], view)

    def dispatch(s, t, task_finish0, worker_finish0, truth, comp, lost_w):
        # -- 0. crash-loss rollback (fault stage ran in the runtime) --------
        with jax.named_scope("simx.megha.rollback"):
            head0 = s.head
            if faults is not None:
                # re-enqueue lost tasks: roll each GM's FIFO head back to the
                # earliest lost position (re-examined over the coming rounds)
                lt0 = jnp.where(lost_w, s.worker_task, T)
                head0 = head0.at[task_gm_pad[lt0]].min(
                    task_pos_pad[lt0], mode="drop"
                )

        # -- 1. completions (truth/comp = the runtime's completion stage) ---
        with jax.named_scope("simx.megha.views"):
            regain = ((s.worker_gm[None, :] == g_col) & (comp & ~s.worker_borrowed))
            view = s.view | regain
            messages = s.messages + jnp.sum(comp, dtype=jnp.int32)  # LM -> GM

            # -- 2. heartbeat (+ GM down windows / recovery resets) ---------
            if faults is None:
                do_hb = (s.rnd % hb) == (hb - 1)
                view = jnp.where(do_hb, truth[None, :], view)
                messages = messages + jnp.where(do_hb, G * L, 0).astype(jnp.int32)
                adopt = None
            else:
                hb_eff = hb + faults.hb_extra_rounds       # delay perturbation
                do_hb = (s.rnd % hb_eff) == (hb_eff - 1)
                adopt, row_active, n_live = gm_adoption(
                    gm_down_mask(faults, t), s.rnd
                )
                view = jnp.where(do_hb, truth[None, :], view)
                messages = messages + jnp.where(do_hb, n_live * L, 0).astype(
                    jnp.int32
                )
                # §3.5 recovery: a returning GM rebuilds its view from LM truth
                rec = gm_recovered_now(faults, t, cfg.dt)
                view = jnp.where(rec[:, None], truth[None, :], view)
                messages = messages + L * jnp.sum(rec, dtype=jnp.int32)

        # -- 3. internal match (FIFO windows, [G, W/G] arrays) --------------
        with jax.named_scope("simx.megha.internal"):
            wtask = rt.slice_rows(gm_tasks, head0, C)                 # int32[G,C]
            wsubmit = rt.slice_rows(submit_c, head0, C)               # float32[G,C]
            fpad = rt.finish_pad(task_finish0)
            launched_w = rt.window_launched(fpad, wtask, T)           # bool[G,C]
            queued_w = ~launched_w & (wsubmit <= t)                   # bool[G,C]
            if faults is not None:
                queued_w = queued_w & row_active[:, None]  # frozen when no GM live
            nq = jnp.sum(queued_w, axis=1, dtype=jnp.int32)           # int32[G]
            fifo = rt.sorted_fifo(queued_w, C)                        # int32[G,C]
            view_eff = view if adopt is None else view[adopt]
            avail_int = view_eff[g_col, int_ord]                      # bool[G,wi]
            ranks_i = match_fn(avail_int, nq)                         # int32[G,wi]
            sel_pos = jnp.take_along_axis(
                fifo, jnp.clip(ranks_i, 0, C - 1), axis=1
            )
            sel_task_i = jnp.where(
                ranks_i >= 0,
                jnp.take_along_axis(wtask, jnp.clip(sel_pos, 0, C - 1), axis=1),
                -1,
            )                                                         # int32[G,wi]
            proposed_i = sel_task_i >= 0
            truth_int = truth[int_ord]                                # bool[G,wi]
            launch_i = proposed_i & truth_int
            invalid_i = proposed_i & ~truth_int
            # flat (g, i) -> worker coordinates via the static inverse perm
            launch_w = launch_i.reshape(-1)[inv_int]                  # bool[W]
            task_w = jnp.where(launch_w, sel_task_i.reshape(-1)[inv_int], T)
            (task_finish, worker_finish, worker_task, worker_gm,
             worker_borrowed) = launch_updates(
                t, launch_w, task_w, part_gm,
                task_finish0, worker_finish0, s.worker_task,
                s.worker_gm, s.worker_borrowed,
            )
            truth = truth & ~launch_w
            # the proposing GM marks every proposed internal worker busy in its
            # own view (popped from the free pool when the batch was built)
            proposed_own = proposed_i.reshape(-1)[inv_int]            # bool[W]
            view = view & ~(proposed_own[None, :] & (part_gm[None, :] == g_col))
            inconsistencies = s.inconsistencies + jnp.sum(invalid_i, dtype=jnp.int32)
            inval_gl = (
                invalid_i[:, :, None] & (lm_int[:, :, None] == l_row)
            ).any(axis=1)
            view = piggyback(view, truth, inval_gl, adopt)
            batch_gl = (
                proposed_i[:, :, None] & (lm_int[:, :, None] == l_row)
            ).any(axis=1)
            messages = messages + 2 * jnp.sum(batch_gl, dtype=jnp.int32)
            if telemetry:
                with jax.named_scope("simx.telemetry"):
                    # per-round counters: launches + piggybacked [GM, LM] view
                    # repairs (§3.4.1), accumulated through the borrow cond's carry
                    tel_launch = jnp.sum(launch_w, dtype=jnp.int32)
                    tel_repair = jnp.sum(inval_gl, dtype=jnp.int32)
            if provenance:
                with jax.named_scope("simx.provenance"):
                    # attempt = every queued task in a GM window (ranked this
                    # round); stale = per-task invalid-proposal increments (the
                    # §3.4 inconsistencies), borrow-phase hits accumulated through
                    # the cond carry like the telemetry scalars
                    prov_attempt = (
                        jnp.zeros(T, jnp.bool_)
                        .at[jnp.where(queued_w, wtask, T)]
                        .set(True, mode="drop")
                    )
                    stale_inc = (
                        jnp.zeros(T, jnp.int32)
                        .at[jnp.where(invalid_i, sel_task_i, T)]
                        .add(1, mode="drop")
                    )

        # -- 4. borrow match (full [G, W] pass, only when queues outrun the
        #       internal views) --------------------------------------------
        with jax.named_scope("simx.megha.borrow"):
            placed_i = jnp.sum(proposed_i, axis=1, dtype=jnp.int32)
            need_borrow = jnp.any(nq > placed_i)

            def borrow(args):
                (view, truth, task_finish, worker_finish, worker_task, worker_gm,
                 worker_borrowed, inconsistencies, repartitions, messages) = args[:10]
                fpad2 = rt.finish_pad(task_finish)
                launched2 = rt.window_launched(fpad2, wtask, T)
                queued2 = ~launched2 & (wsubmit <= t)
                if faults is not None:
                    queued2 = queued2 & row_active[:, None]
                nq2 = jnp.sum(queued2, axis=1, dtype=jnp.int32)
                fifo2 = rt.sorted_fifo(queued2, C)
                view_b = view if adopt is None else view[adopt]
                avail_ord = jnp.take_along_axis(view_b, orders, axis=1)  # bool[G,W]
                ranks = match_fn(avail_ord, nq2)                       # int32[G,W]
                sel_pos2 = jnp.take_along_axis(
                    fifo2, jnp.clip(ranks, 0, C - 1), axis=1
                )
                sel_task = jnp.where(
                    ranks >= 0,
                    jnp.take_along_axis(wtask, jnp.clip(sel_pos2, 0, C - 1), axis=1),
                    -1,
                )
                # ordered positions -> worker coordinates (inverse gather)
                prop = jnp.take_along_axis(sel_task, inv_orders, axis=1)
                proposed = prop >= 0
                repartitions = repartitions + jnp.sum(
                    proposed & (part_gm[None, :] != g_col), dtype=jnp.int32
                )
                # simultaneous claims: per-round rotating GM priority, one
                # min-reduction over (priority, gm) packed into a single int
                pri = (g_col + s.rnd) % G
                enc = jnp.where(
                    proposed, jnp.broadcast_to(pri * G, (G, W)) + g_col, G * G
                )
                win_enc = jnp.min(enc, axis=0)                         # int32[W]
                any_prop = win_enc < G * G
                win_g = jnp.where(any_prop, win_enc % G, 0)
                launch = any_prop & truth                              # bool[W]
                win_task = jnp.where(launch, prop[win_g, w_row], T)
                (task_finish, worker_finish, worker_task, worker_gm,
                 worker_borrowed) = launch_updates(
                    t, launch, win_task, win_g,
                    task_finish, worker_finish, worker_task,
                    worker_gm, worker_borrowed,
                )
                truth = truth & ~launch
                view = view & ~proposed
                launched_by_g = launch[None, :] & (g_col == win_g[None, :])
                invalid = proposed & ~launched_by_g                    # bool[G,W]
                inconsistencies = inconsistencies + jnp.sum(invalid, dtype=jnp.int32)
                inval2_gl = invalid.reshape(G, L, wpl).any(axis=2)
                view = piggyback(view, truth, inval2_gl, adopt)
                batch2 = proposed.reshape(G, L, wpl).any(axis=2)
                messages = messages + 2 * jnp.sum(batch2, dtype=jnp.int32)
                out = (view, truth, task_finish, worker_finish, worker_task,
                       worker_gm, worker_borrowed, inconsistencies, repartitions,
                       messages)
                if telemetry:
                    with jax.named_scope("simx.telemetry"):
                        out = out + (
                            args[10] + jnp.sum(launch, dtype=jnp.int32),
                            args[11] + jnp.sum(inval2_gl, dtype=jnp.int32),
                        )
                if provenance:
                    with jax.named_scope("simx.provenance"):
                        out = out + (
                            args[-1]
                            + jnp.zeros(T, jnp.int32)
                            .at[jnp.where(invalid, prop, T)]
                            .add(1, mode="drop"),
                        )
                return out

            carry = (view, truth, task_finish, worker_finish, worker_task,
                     worker_gm, worker_borrowed, inconsistencies, s.repartitions,
                     messages)
            if telemetry:
                carry = carry + (tel_launch, tel_repair)
            if provenance:
                carry = carry + (stale_inc,)
            carry = jax.lax.cond(need_borrow, borrow, lambda a: a, carry)
            (view, truth, task_finish, worker_finish, worker_task, worker_gm,
             worker_borrowed, inconsistencies, repartitions, messages) = carry[:10]
            if telemetry:
                tel_launch, tel_repair = carry[10], carry[11]
            if provenance:
                stale_inc = carry[-1]

        # -- 5. advance each GM's FIFO head past its launched prefix --------
        with jax.named_scope("simx.megha.head"):
            fpad3 = rt.finish_pad(task_finish)
            launched3 = rt.window_launched(fpad3, wtask, T)            # bool[G,C]
            head = jnp.minimum(head0 + rt.launched_lead(launched3), gm_len)

            upd = dict(
                task_finish=task_finish,
                head=head,
                worker_finish=worker_finish,
                worker_task=worker_task,
                worker_gm=worker_gm,
                worker_borrowed=worker_borrowed,
                view=view,
                inconsistencies=inconsistencies,
                repartitions=repartitions,
                messages=messages,
            )
        if telemetry:
            upd["telemetry"] = dict(
                launches=tel_launch, view_repairs=tel_repair
            )
        if provenance:
            upd["provenance"] = dict(
                attempt=prov_attempt, stale=stale_inc, authority=worker_gm
            )
        return upd

    return rt.compose_step(
        cfg, tasks, dispatch, faults, telemetry=telemetry, provenance=provenance
    )


def simulate_fixed(
    cfg: SimxConfig,
    tasks: TaskArrays,
    seed: jax.Array | int,
    num_rounds: int,
    match_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
) -> MeghaState:
    """Run exactly ``num_rounds`` rounds from a fresh DC — a pure function of
    ``seed`` (and the ``faults`` leaves), so an entire sweep grid runs as
    ``jax.vmap(simulate_fixed, ...)`` in one compiled program.  Thin
    wrapper over the registry-driven ``runtime.simulate_fixed``."""
    return rt.simulate_fixed(
        "megha", cfg, tasks, seed, num_rounds, match_fn=match_fn, faults=faults
    )


def _build_step(
    cfg: SimxConfig,
    tasks: TaskArrays,
    key: jax.Array,
    *,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    faults: FaultSchedule | None = None,
    telemetry: bool = False,
    provenance: bool = False,
) -> Callable[[MeghaState], MeghaState]:
    del pick_fn  # megha has no reservation queues
    return make_megha_step(
        cfg, tasks, gm_orders(key, cfg), match_fn, faults=faults,
        telemetry=telemetry, provenance=provenance,
    )


RULE = rt.register_rule(
    rt.Rule(
        name="megha",
        init=lambda cfg, tasks: init_megha_state(cfg, tasks.num_tasks),
        build_step=_build_step,
        needs_grid=True,
    )
)
