"""Jit'd user-facing wrappers around the Pallas kernels.

``match_tasks`` is the vectorized GM match operation used by the serving
engine and the SDPS benchmarks; it composes the Pallas rank kernel with a
cheap inverse scatter (task -> worker position).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import match as match_kernel
from repro.kernels import ref


@functools.partial(
    jax.jit, static_argnames=("max_tasks", "use_pallas", "block_rows")
)
def match_tasks(
    avail: jax.Array,
    n_tasks: jax.Array | int,
    max_tasks: int,
    *,
    use_pallas: bool = True,
    block_rows: int = 64,
) -> tuple[jax.Array, jax.Array]:
    """Match up to ``n_tasks`` tasks onto free workers in priority order.

    Args:
      avail: bool/int8[W] availability in the GM's priority order.
      n_tasks: dynamic scalar, clamped to ``max_tasks``.
      max_tasks: static output size.

    Returns:
      assignment: int32[max_tasks] ordered-worker position per task (-1 if
        unplaced).
      placed: int32[] count of placed tasks.
    """
    n = jnp.minimum(jnp.asarray(n_tasks, jnp.int32), max_tasks)
    if use_pallas:
        ranks = match_kernel.match_ranks(avail, n, block_rows=block_rows)
    else:
        ranks = ref.match_ranks_ref(avail, n)
    w = avail.shape[0]
    out = jnp.full((max_tasks,), -1, jnp.int32)
    # -1 ranks must not wrap to index -1: remap them OOB so mode="drop" drops
    idx = jnp.where(ranks >= 0, ranks, max_tasks)
    out = out.at[idx].set(jnp.arange(w, dtype=jnp.int32), mode="drop")
    placed = jnp.sum((ranks >= 0).astype(jnp.int32))
    return out, placed


@jax.jit
def verify_and_commit(
    truth: jax.Array, assignment: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """LM-side verification (§3.3): check each assignment against ground
    truth, commit the valid ones (mark busy), report the invalid ones.

    Args:
      truth: bool[W] authoritative availability at the LM.
      assignment: int32[T] worker positions (-1 = no-op).

    Returns:
      (new_truth, valid): updated availability; bool[T] validity per task.

    Note: duplicate assignments to the same worker within one batch are
    resolved first-wins, matching the LM's sequential iteration over the
    batch (§3.4.1) — implemented with a segment-min over task indices.
    """
    w = truth.shape[0]
    t = assignment.shape[0]
    safe = jnp.clip(assignment, 0, w - 1)
    # first task index claiming each worker; -1 assignments scatter OOB (w)
    # so they can't steal first-claim at worker 0
    claim_idx = jnp.where(assignment >= 0, assignment, w)
    first = jnp.full((w,), t, jnp.int32).at[claim_idx].min(
        jnp.arange(t, dtype=jnp.int32), mode="drop"
    )
    is_first = first[safe] == jnp.arange(t, dtype=jnp.int32)
    valid = (assignment >= 0) & truth[safe] & is_first
    # commit via a claimed-mask (duplicate-safe: a later invalid duplicate
    # must not scatter the worker back to free)
    claimed = jnp.zeros_like(truth).at[jnp.where(valid, safe, w)].set(
        True, mode="drop"
    )
    return truth & ~claimed, valid


@jax.jit
def release(truth: jax.Array, workers: jax.Array) -> jax.Array:
    """Mark completed tasks' workers free again (-1 entries are no-ops)."""
    safe = jnp.clip(workers, 0, truth.shape[0] - 1)
    upd = jnp.where(workers >= 0, True, truth[safe])
    return truth.at[safe].set(upd, mode="drop")
