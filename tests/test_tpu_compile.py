"""Compile the main path for a TPU v5e without the chip.

The TPU compiler is part of the installation, and it compiles for a chip
that is described and not attached.  These tests compile the Pallas match
kernel with ``interpret=False`` at the simulator's real widths — megha's
``[G, 50k]`` GM rows, the oracle's ``[1, 50k]`` global row and the
``[50k, R]`` head-of-queue pick with ``block_rows=1`` — and one rule's
32-round chunk runner at 50k workers.  Nothing runs, so they say nothing
about results or time; they catch what the chip's compiler refuses (the
kernel's old in-tile ``cumsum`` had no Mosaic lowering) before a chip run.

The topology is described only inside a fixture: the TPU library may be
loaded by one process at a time, and describing it while a module is
imported would make parallel test workers collect different tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.megha import grid_workers
from repro.kernels.match import match_ranks_batched
from repro.simx import engine
from repro.simx.runtime import get_rule
from repro.simx.state import SimxConfig, export_workload
from repro.workload.synth import synthetic_trace

W = 50_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to an active persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "g,w,block_rows",
    [(8, W, 64), (1, W, 64), (W, 64, 1)],
    ids=["megha_gm_rows", "oracle_row", "queue_pick"],
)
def test_match_kernel_compiles_for_v5e(one_chip, g, w, block_rows):
    avail = jax.ShapeDtypeStruct((g, w), jnp.int8, sharding=one_chip)
    n = jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(
            lambda a, n: match_ranks_batched(
                a, n, block_rows=block_rows, interpret=False
            )
        )
        .lower(avail, n)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_megha_chunk_runner_compiles_for_v5e(one_chip):
    """The jnp-match megha round scan at 50k workers (the paper's largest
    cluster), as ``run_to_completion`` compiles it."""
    nw = grid_workers(W, 8, 8)
    cfg = SimxConfig(num_workers=nw, dt=0.01)
    tasks = export_workload(
        synthetic_trace(num_jobs=4, tasks_per_job=1000, num_workers=nw)
    )
    rule = get_rule("megha")
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        rule.init(cfg, tasks),
    )
    compiled = engine.make_chunk_runner(step, chunk=32).lower(state).compile()
    assert compiled.memory_analysis() is not None
