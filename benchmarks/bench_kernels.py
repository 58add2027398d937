"""Kernel microbenchmarks: the Pallas match kernel (interpret mode on CPU —
wall-times are NOT TPU times; the derived column carries the bytes moved
and the problem sizes) and the serving-engine placement round."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *a, iters=10):
    fn(*a)[0].block_until_ready() if isinstance(fn(*a), tuple) else None
    t0 = time.time()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run(full: bool = False) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)
    for w in (8192, 65536):
        avail = jnp.asarray((rng.random(w) < 0.5).astype(np.int8))
        us_ref = _time(lambda a: ops.match_tasks(a, 512, 512, use_pallas=False), avail)
        us_pal = _time(lambda a: ops.match_tasks(a, 512, 512, use_pallas=True), avail)
        rows.append(f"kernel_match_jnp_w{w},{us_ref:.1f},bytes_in={w}")
        rows.append(f"kernel_match_pallas_interp_w{w},{us_pal:.1f},bytes_in={w}")
    truth = jnp.ones((65536,), bool)
    asg = jnp.asarray(rng.integers(0, 65536, 512), jnp.int32)
    us = _time(lambda t, a: ops.verify_and_commit(t, a), truth, asg)
    rows.append(f"kernel_verify_commit_w65536,{us:.1f},batch=512")
    return rows
