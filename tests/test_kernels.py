"""Pallas match kernel vs jnp oracle: shape/dtype sweeps + hypothesis
property tests on the scheduler-state invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import require_or_skip_hypothesis

require_or_skip_hypothesis()
from hypothesis import given, settings, strategies as st

from repro.core import fastpath as FP
from repro.kernels import ops, ref
from repro.kernels.match import match_ranks, match_ranks_batched


@pytest.mark.parametrize("w", [1, 100, 128, 1024, 8192, 50_000])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int32, jnp.bool_])
def test_match_kernel_allclose_shapes_dtypes(w, dtype):
    rng = np.random.default_rng(w)
    avail = (rng.random(w) < 0.4)
    a = jnp.asarray(avail).astype(dtype)
    for n in (0, 1, w // 2, w):
        got = match_ranks(a, n)
        want = ref.match_ranks_ref(a, n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("block_rows", [8, 64, 256])
def test_match_kernel_block_shape_invariance(block_rows):
    rng = np.random.default_rng(0)
    a = jnp.asarray((rng.random(4096) < 0.5).astype(np.int8))
    got = match_ranks(a, 1000, block_rows=block_rows)
    want = ref.match_ranks_ref(a, 1000)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize(
    "case,w,block_rows",
    [
        ("n_zero", 1000, 8),            # no task to place: every rank is -1
        ("n_above_free", 1000, 8),      # fewer free workers than tasks
        ("all_free", 4096, 8),          # every lane and row of a tile is free
        ("ragged_w", 3 * 1024 + 77, 8),  # W not a multiple of the block
        ("one_row_blocks", 2 * 128 + 5, 1),  # the [W, R] pick's tile
    ],
)
def test_tile_scan_edge_cases(case, w, block_rows):
    """The in-tile triangular-matmul scan against the jnp cumsum, at the
    edges of a tile: the row offsets, the cross-block carry and the
    padding of the last block all take part."""
    rng = np.random.default_rng(w)
    g = 3
    if case == "all_free":
        avail = np.ones((g, w), np.int8)
    else:
        avail = (rng.random((g, w)) < 0.4).astype(np.int8)
    free = avail.sum(axis=1)
    n = {
        "n_zero": np.zeros(g),
        "n_above_free": free + 5,
        "all_free": np.array([0, w // 2, w]),
    }.get(case, rng.integers(0, w + 1, g))
    a, nt = jnp.asarray(avail), jnp.asarray(n, jnp.int32)
    got = np.asarray(match_ranks_batched(a, nt, block_rows=block_rows))
    want = np.asarray(ref.match_ranks_batched_ref(a, nt))
    np.testing.assert_array_equal(got, want)
    if case == "n_zero":
        assert (got == -1).all()
    if case == "n_above_free":
        assert ((got >= 0).sum(axis=1) == free).all()


@settings(max_examples=30, deadline=None)
@given(
    w=st.integers(1, 500),
    n=st.integers(0, 600),
    p=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**31),
)
def test_match_semantics_property(w, n, p, seed):
    """Ranks are exactly 0..K-1 over free workers in order, K=min(n,#free)."""
    rng = np.random.default_rng(seed)
    avail = (rng.random(w) < p).astype(np.int8)
    ranks = np.asarray(ref.match_ranks_ref(jnp.asarray(avail), n))
    taken = ranks[ranks >= 0]
    k = min(n, int(avail.sum()))
    assert len(taken) == k
    assert sorted(taken) == list(range(k))
    # assigned positions are the FIRST k free workers (priority order)
    free_pos = np.flatnonzero(avail)
    np.testing.assert_array_equal(np.flatnonzero(ranks >= 0), free_pos[:k])


@settings(max_examples=30, deadline=None)
@given(
    w=st.integers(4, 300),
    t=st.integers(1, 64),
    seed=st.integers(0, 2**31),
)
def test_verify_commit_properties(w, t, seed):
    """No double-booking; conservation; invalid tasks change nothing."""
    rng = np.random.default_rng(seed)
    truth = jnp.asarray(rng.random(w) < 0.6)
    asg = jnp.asarray(rng.integers(-1, w, t), jnp.int32)
    new_truth, valid = ops.verify_and_commit(truth, asg)
    a = np.asarray(asg)
    v = np.asarray(valid)
    # 1) each worker granted to at most one task
    granted = a[v]
    assert len(set(granted.tolist())) == len(granted)
    # 2) granted workers were free and are now busy
    assert all(bool(truth[x]) and not bool(new_truth[x]) for x in granted)
    # 3) conservation: busy count increases exactly by #valid
    assert int(truth.sum()) - int(new_truth.sum()) == int(v.sum())
    # 4) -1 never valid
    assert not v[a < 0].any() if (a < 0).any() else True


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31), n=st.integers(0, 128))
def test_gm_round_conservation(seed, n):
    rng = np.random.default_rng(seed)
    w, g, l = 256, 4, 4
    orders = FP.make_orders(w, g, l, seed=seed % 97)
    truth = jnp.asarray(rng.random(w) < 0.7)
    view = jnp.asarray(rng.random(w) < 0.7)
    res = FP.gm_round(truth, view, orders[0], n, max_tasks=128, use_pallas=False)
    placed = int((res.workers >= 0).sum())
    assert int(truth.sum()) - int(res.truth.sum()) == placed
    # placements unique
    ws = np.asarray(res.workers)
    ws = ws[ws >= 0]
    assert len(set(ws.tolist())) == len(ws)
    # view repair: on any inconsistency the view equals ground truth
    if int(res.n_inconsistent) > 0:
        assert bool(jnp.array_equal(res.view, res.truth))


def test_match_tasks_inverse_scatter():
    avail = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.int8)
    out, placed = ops.match_tasks(avail, 3, 4, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(out), [0, 2, 3, -1])
    assert int(placed) == 3
