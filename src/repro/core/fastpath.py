"""Vectorized (JAX) Megha state machine — the TPU-native fast path.

The event simulator in ``megha.py`` is the faithful reference; this module
re-expresses one GM scheduling round as fixed-shape array ops so that a
frontend router can make tens of thousands of placement decisions per second
(§2.3.2 targets 40k-1M SDPS).  Used by ``serve/engine.py`` and the SDPS
benchmark.

State layout (single resource unit per worker, §4.1):
  truth:  bool[W]    — authoritative availability (conceptually sharded per
                       LM; kept as one array here, the LM boundary is a
                       partition of the index space)
  view:   bool[G, W] — each GM's eventually-consistent copy
  order:  int32[G, W] — each GM's priority permutation over workers
                       (internal partitions first, then external, shuffled
                       per GM per §3.3)

One round = match (Pallas kernel) -> verify-and-commit at the LM ->
inconsistency repair (failed tasks reported back; view refreshed from the
piggybacked truth).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops


def make_orders(
    num_workers: int, num_gms: int, num_lms: int, seed: int = 0
) -> jax.Array:
    """Per-GM priority permutations: own partitions (shuffled) first, then
    external partitions (shuffled), mirroring GlobalManager.__init__."""
    if num_workers % (num_gms * num_lms):
        raise ValueError("num_workers must divide evenly into GM x LM partitions")
    wpl = num_workers // num_lms
    psz = wpl // num_gms
    rng = np.random.default_rng(seed)
    orders = np.empty((num_gms, num_workers), np.int32)
    for g in range(num_gms):
        internal, external = [], []
        for l in range(num_lms):
            for g2 in range(num_gms):
                part = np.arange(l * wpl + g2 * psz, l * wpl + (g2 + 1) * psz)
                (internal if g2 == g else external).append(part)
        internal = np.concatenate(internal)
        external = np.concatenate(external)
        rng.shuffle(internal)
        rng.shuffle(external)
        orders[g] = np.concatenate([internal, external])
    return jnp.asarray(orders)


class RoundResult(NamedTuple):
    truth: jax.Array        # updated ground truth
    view: jax.Array         # updated GM view (repaired on inconsistency)
    workers: jax.Array      # int32[max_tasks] worker id per task, -1 unplaced
    valid: jax.Array        # bool[max_tasks] LM verification verdict
    n_inconsistent: jax.Array


@functools.partial(jax.jit, static_argnames=("max_tasks", "use_pallas"))
def gm_round(
    truth: jax.Array,
    view: jax.Array,
    order: jax.Array,
    n_tasks: jax.Array | int,
    *,
    max_tasks: int,
    use_pallas: bool = True,
) -> RoundResult:
    """One GM scheduling round against the LM ground truth.

    1. match: rank free workers in the GM's (stale) view, priority order.
    2. verify-and-commit: the LM checks each mapping against truth; valid
       mappings launch (truth := busy), invalid ones are inconsistencies.
    3. repair: the GM marks its placements busy in its view; on any
       inconsistency the piggybacked LM state overwrites the view (§3.4.1 —
       we refresh the full view; per-LM granularity is a strict refinement).
    """
    avail_ordered = view[order]  # GM's priority-ordered availability
    asg_pos, _ = kops.match_tasks(
        avail_ordered, n_tasks, max_tasks, use_pallas=use_pallas
    )
    workers = jnp.where(asg_pos >= 0, order[jnp.clip(asg_pos, 0, order.shape[0] - 1)], -1)
    new_truth, valid = kops.verify_and_commit(truth, workers)
    n_bad = jnp.sum((workers >= 0) & ~valid)
    # GM view: mark everything we tried as busy ...
    safe = jnp.clip(workers, 0, view.shape[0] - 1)
    view2 = view.at[safe].set(jnp.where(workers >= 0, False, view[safe]), mode="drop")
    # ... and on inconsistency adopt the piggybacked truth wholesale.
    view3 = jnp.where(n_bad > 0, new_truth, view2)
    workers_final = jnp.where(valid, workers, -1)
    return RoundResult(new_truth, view3, workers_final, valid, n_bad)


@jax.jit
def complete(
    truth: jax.Array, view: jax.Array, workers: jax.Array, borrowed: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Task completions: free workers in truth; the scheduling GM's view only
    regains NON-borrowed workers (§3.4 — borrowed ones wait for a heartbeat)."""
    truth2 = kops.release(truth, workers)
    keep = (workers >= 0) & ~borrowed
    safe = jnp.clip(workers, 0, view.shape[0] - 1)
    view2 = view.at[safe].set(jnp.where(keep, True, view[safe]), mode="drop")
    return truth2, view2


@jax.jit
def heartbeat(view: jax.Array, truth: jax.Array, lm_slice: jax.Array) -> jax.Array:
    """Periodic LM state update: overwrite the view for one LM's index range.
    ``lm_slice`` is a bool[W] mask selecting that LM's workers."""
    return jnp.where(lm_slice, truth, view)
