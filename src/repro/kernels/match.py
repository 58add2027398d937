"""Pallas TPU kernel for the Megha GM match operation.

The GM's hot loop — "walk my priority-ordered view of up to 50k workers and
hand the next free worker to each queued task" (§3.2) — is a sequential
pointer chase in the paper's Python prototype.  On TPU we reformulate it as a
*rank-and-select*: a prefix-sum over the availability bit-vector gives every
free worker its task rank in one data-parallel pass, as a grid-strided
blocked scan with a scalar carry in SMEM.

Layout: the 1-D worker axis is reshaped to (rows, 128) so each VMEM block is
a hardware-aligned (block_rows, 128) tile.  The grid walks row-blocks in
order; ``carry_ref`` (SMEM) accumulates the running count of free workers so
block b's local prefix becomes a global rank.  TPU grid iteration is
sequential on a core, which makes the scalar carry safe — this is the
standard TPU alternative to a GPU decoupled-lookback scan.

The in-tile prefix is two triangular matmuls, because Mosaic has no
lowering for ``cumsum``: each row's lanes against a 128x128 upper-triangular
ones matrix (the prefix within the row), plus each row's offset from a
strictly-lower ``[block_rows, block_rows]`` ones matrix against the tile
(the sum of the rows above it).  Operands are 0/1 (at most int8) in f32
with f32 accumulation, so every partial sum is an integer below 2**24 and
the scan is exact.

``interpret=None`` (the default) runs the interpreter only where the
default backend is the CPU, which has no Mosaic; on a TPU the kernel is
compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def default_interpret() -> bool:
    """Interpret the kernel iff the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def _tile_prefix(a: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a (rows, 128) f32 tile in row-major order."""
    rows = a.shape[0]
    li = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (li <= lj).astype(jnp.float32)
    pre = jnp.dot(a, upper, preferred_element_type=jnp.float32)
    if rows > 1:
        ri = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
        rj = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        lower = (rj < ri).astype(jnp.float32)
        above = jnp.dot(lower, a, preferred_element_type=jnp.float32)
        pre = pre + jnp.sum(above, axis=1, keepdims=True)
    return pre


def _match_kernel_batched(n_tasks_ref, avail_ref, out_ref, carry_ref):
    """One (1, block_rows, 128) tile of one GM's rank-and-select scan.

    Grid is (G, row_blocks); TPU iterates the trailing grid dim fastest, so
    each GM g walks its row-blocks b = 0..B-1 in order and the SMEM carry is
    reset at b == 0 — G independent blocked scans in one kernel launch.
    """
    g = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        carry_ref[0] = 0

    a = avail_ref[0].astype(jnp.int32)  # (block_rows, 128)
    af = a.astype(jnp.float32)
    carry = carry_ref[0]
    rank = _tile_prefix(af).astype(jnp.int32) - 1 + carry
    take = (a > 0) & (rank < n_tasks_ref[g])
    out_ref[0] = jnp.where(take, rank, -1)
    carry_ref[0] = carry + jnp.sum(af).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def match_ranks_batched(
    avail: jax.Array,
    n_tasks: jax.Array,
    *,
    block_rows: int = 64,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched ``match_ranks``: all GMs match in one kernel launch.

    Args:
      avail: int8/int32/bool[G, W] — per-GM availability, each row in that
        GM's priority order; W padded to a multiple of ``block_rows * 128``.
      n_tasks: int32[G] — tasks each GM wants to place.
      block_rows: sublane rows per VMEM block; the block is
        (block_rows, 128) int8 = block_rows*128 bytes — e.g. 64 rows = 8 KiB
        in, 32 KiB out, far under the ~16 MiB VMEM budget, leaving room for
        double buffering.
      interpret: run the Pallas interpreter; ``None`` derives it from the
        default backend (``default_interpret``).

    Returns: int32[G, W] per-GM task ranks, -1 where no task is assigned.
    """
    if interpret is None:
        interpret = default_interpret()
    g, w = avail.shape
    block = block_rows * LANES
    w_pad = -(-w // block) * block
    a = jnp.zeros((g, w_pad), jnp.int8).at[:, :w].set(avail.astype(jnp.int8))
    a3 = a.reshape(g, w_pad // LANES, LANES)
    n = jnp.asarray(n_tasks, jnp.int32).reshape(g)

    grid = (g, w_pad // block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # n_tasks rides in SMEM ahead of the grid
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_rows, LANES), lambda g, b, n: (g, b, 0))],
        out_specs=pl.BlockSpec((1, block_rows, LANES), lambda g, b, n: (g, b, 0)),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )
    out = pl.pallas_call(
        _match_kernel_batched,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, w_pad // LANES, LANES), jnp.int32),
        interpret=interpret,
    )(n, a3)
    return out.reshape(g, -1)[:, :w]


def match_ranks(
    avail: jax.Array,
    n_tasks: jax.Array | int,
    *,
    block_rows: int = 64,
) -> jax.Array:
    """Per-worker task ranks for one GM: ``match_ranks_batched`` on a
    single row.

    Args:
      avail: int8/int32/bool[W] availability in GM priority order.
      n_tasks: tasks to place (dynamic scalar ok).

    Returns: int32[W] task rank per ordered worker position, -1 if none.
    """
    n = jnp.asarray(n_tasks, jnp.int32).reshape(1)
    return match_ranks_batched(avail[None], n, block_rows=block_rows)[0]
