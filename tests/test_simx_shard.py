"""Mesh-sharded sweep executors (``repro.simx.shard``).

Parity-first, like the streaming suite: the sharded drivers are
*executors* for the same grid programs the serial path runs (one shared
``fig2_plan`` / ``fig4_plan`` builds byte-identical inputs for both), so
every pin here is sharded-vs-serial equality — p50/p95 grids allclose at
rtol 1e-5 for all five rules, exact completion counts, and exact
steady-state lane observables.  The grid sizes are deliberately
indivisible (15 points, 3 lanes) so the pad-to-device-multiple /
slice-off-the-host contract is always exercised on multi-device hosts.

The suite adapts to however many devices the process has: under plain
tier-1 (1 CPU device) the mesh paths still run — degenerate but real —
and under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
sharded-smoke step) the same tests pin true multi-device parity.

``test_fault_grid_is_seed_sensitive`` is a regression pin for the bug
class that forced the pmap executor: under jax 0.4.37 a hand-written
``shard_map`` broadcast shard 0's per-point PRNG key to every device, an
error that fixed-seed grids cannot see (on JAX 0.9 ``pmap`` itself is
built on ``shard_map``).  It asserts distinct per-point seeds produce
their own (serial-matching) numbers through the sharded path.
"""

import functools

import numpy as np
import pytest

import jax

from repro.simx import shard as sxsh
from repro.simx import sweep as sxs
from repro.simx.runtime import RULES
from repro.simx.stream import run_steady_state
from repro.workload.synth import PoissonArrivals, fixed_job_factory

N_DEV = jax.device_count()

#: 5 loads x 3 seeds = 15 points — indivisible by 8, so the forced-device
#: CI run always pads (15 -> 16) and slices
FIG2 = dict(
    loads=(0.35, 0.55, 0.7, 0.85, 0.95), num_seeds=3, num_workers=64,
    num_jobs=6, tasks_per_job=8, dt=0.05, num_gms=2, num_lms=2,
)
FIG4 = dict(
    fractions=(0.0, 0.05, 0.1), num_seeds=2, num_workers=64, num_jobs=6,
    tasks_per_job=8, dt=0.05, num_gms=2, num_lms=2,
)
STEADY = dict(
    window_jobs=16, window_tasks=128, rounds_per_refill=16,
    num_gms=2, num_lms=2,
)
STEADY_W = 64
STEADY_LOADS = (0.5, 0.9)


def _close(a, b, **kw):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, equal_nan=True, **kw
    )


@functools.lru_cache(maxsize=None)
def _fig2_pair(rule):
    """(serial, sharded) fig2 results off one shared plan."""
    plan = sxs.fig2_plan(rule, **FIG2)
    serial = sxs.sweep_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, plan.num_rounds,
        match_fn=plan.match_fn, pick_fn=plan.pick_fn,
    )
    sharded = sxsh.sharded_sweep_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, plan.num_rounds,
        match_fn=plan.match_fn, pick_fn=plan.pick_fn,
        mesh=sxsh.sweep_mesh(),
    )
    return serial, sharded


@pytest.mark.parametrize("rule", sorted(RULES))
def test_fig2_parity(rule):
    serial, sharded = _fig2_pair(rule)
    assert set(sharded) == set(serial)
    L, S = len(FIG2["loads"]), FIG2["num_seeds"]
    for key in ("p50", "p95", "mean", "mean_util"):
        assert sharded[key].shape == (L, S)
        _close(sharded[key], serial[key], err_msg=f"{rule}:{key}")
    for key in ("tasks_done", "jobs_done", "lost", "messages", "probes"):
        np.testing.assert_array_equal(
            np.asarray(sharded[key]), np.asarray(serial[key]),
            err_msg=f"{rule}:{key}",
        )


@pytest.mark.parametrize("rule", sorted(RULES))
def test_fig4_parity(rule):
    serial = sxs.fig4_sweep(rule, **FIG4)
    sharded = sxsh.sharded_fig4_sweep(rule, mesh=sxsh.sweep_mesh(), **FIG4)
    assert int(sharded["n_devices"]) == N_DEV
    for key in ("p50", "p95", "mean"):
        _close(sharded[key], serial[key], err_msg=f"{rule}:{key}")
    for key in ("tasks_done", "lost"):
        np.testing.assert_array_equal(
            np.asarray(sharded[key]), np.asarray(serial[key]),
            err_msg=f"{rule}:{key}",
        )


def test_fault_grid_is_seed_sensitive():
    """Distinct per-point seeds must each produce their own numbers through
    the sharded executor (regression: the shard_map lowering collapsed the
    per-point PRNG key to global entry 0's, so every device simulated the
    same seed — silently, because fixed-seed grids still agreed)."""
    spec = dict(FIG4, num_seeds=4)
    serial = sxs.fig4_sweep("megha", **spec)
    sharded = sxsh.sharded_fig4_sweep("megha", mesh=sxsh.sweep_mesh(), **spec)
    _close(sharded["p50"], serial["p50"])
    _close(sharded["p95"], serial["p95"])
    # the serial grid itself must vary across the seed axis somewhere, or
    # this test could never catch a seed collapse
    row_spread = np.ptp(np.asarray(serial["p95"]), axis=1)
    assert np.any(row_spread > 0), (
        "fig4 grid is seed-insensitive; the parity pin above is vacuous"
    )


def _one_shot_grid(plan):
    """The sharded Fig. 2 grid as one program run to the whole budget, the
    form the chunked runner replaced: ``simulate_fixed`` and
    ``point_summary`` per point, vmapped per device under ``jax.pmap``."""
    import dataclasses

    import jax.numpy as jnp

    from repro.simx import runtime

    mesh = sxsh.sweep_mesh()
    flat, rows, cols = sxsh.make_grid_shard(
        plan.submit_grid, plan.job_submit_grid, plan.seeds)
    has_queues = runtime.get_rule(plan.name).has_queues

    def point(g):
        tk = dataclasses.replace(
            plan.tasks, submit=g.submit, job_submit=g.job_submit)
        state = runtime.simulate_fixed(
            plan.name, plan.cfg, tk, g.seed, plan.num_rounds,
            match_fn=plan.match_fn, pick_fn=plan.pick_fn)
        return sxs.point_summary(state, tk, has_queues=has_queues,
                                 dt=plan.cfg.dt)

    n_dev = int(mesh.devices.size)
    batch, n_pad = sxsh.pad_batch(flat, rows * cols, n_dev)
    batch = jax.tree.map(
        lambda x: jnp.reshape(x, (n_dev, n_pad // n_dev) + x.shape[1:]), batch)
    out = jax.pmap(jax.vmap(point), devices=list(mesh.devices.reshape(-1)))(
        batch)
    return {k: np.asarray(v).reshape((n_pad,) + v.shape[2:])[:rows * cols]
            .reshape((rows, cols) + v.shape[2:]) for k, v in out.items()}


@pytest.mark.parametrize("rule", sorted(RULES))
def test_chunked_grid_equals_the_whole_budget_bitwise(rule):
    """Whole chunks and an uneven remainder (235 = 3 x 64 + 43 rounds)
    give summaries bit-identical to the grid run to its budget in one
    program, to ``sharded_fig2_sweep`` and to the serial ``fig2_sweep``."""
    plan = sxs.fig2_plan(rule, **FIG2)
    assert plan.num_rounds % 64
    grid = sxsh.fig2_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, chunk=64, mesh=sxsh.sweep_mesh(),
        match_fn=plan.match_fn, pick_fn=plan.pick_fn,
    )
    chunked = grid.summary(grid.run(grid.init(), plan.num_rounds))
    serial, _ = _fig2_pair(rule)
    sweep = sxsh.sharded_fig2_sweep(rule, mesh=sxsh.sweep_mesh(), **FIG2)
    for want in (_one_shot_grid(plan), serial, sweep):
        for key, v in want.items():
            if key in chunked:
                np.testing.assert_array_equal(
                    np.asarray(chunked[key]), np.asarray(v),
                    err_msg=f"{rule}:{key}")


#: 2 loads x 3 seeds: 6 points, padded on 4 or 8 devices
SMALL = dict(FIG2, loads=(0.35, 0.95))


@pytest.mark.parametrize("rule", sorted(RULES))
def test_every_point_equals_its_single_datacenter_runner(rule):
    """After ``k`` chunks each grid point's state is, leaf for leaf, the
    state ``engine.make_chunk_runner`` reaches in ``k`` chunks on that
    load's trace and that seed."""
    import dataclasses

    from repro.simx import engine, runtime

    plan = sxs.fig2_plan(rule, **SMALL)
    chunk, k = 16, 3
    grid = sxsh.fig2_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, chunk=chunk,
        mesh=sxsh.sweep_mesh(), match_fn=plan.match_fn, pick_fn=plan.pick_fn,
    )
    carry = grid.init()
    for _ in range(k):
        carry, _ = grid.runner(carry, grid.batch)
    got = jax.tree.map(np.asarray, grid.gather(carry))
    rule_ = runtime.get_rule(rule)
    for i in range(len(SMALL["loads"])):
        tk = dataclasses.replace(
            plan.tasks, submit=plan.submit_grid[i],
            job_submit=plan.job_submit_grid[i])
        for j, seed in enumerate(np.asarray(plan.seeds)):
            step = runtime.point_step(
                rule, plan.cfg, tk, int(seed), match_fn=plan.match_fn,
                pick_fn=plan.pick_fn)
            runner = engine.make_chunk_runner(step, chunk)
            state = rule_.init(plan.cfg, tk)
            for _ in range(k):
                state, _ = runner(state)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    a[i, j], np.asarray(b), err_msg=f"{rule} load {i} seed {j}"),
                got, state)
            assert int(state.rnd) == k * chunk


def test_done_flags_turn_true_exactly_for_finished_points():
    """A point's flag reads true in the chunk its trace finished and after,
    false before: the flags agree with every point's state after every
    chunk, and at some chunk the fast points are done and the slow not."""
    plan = sxs.fig2_plan("megha", **SMALL)
    grid = sxsh.fig2_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, chunk=16, mesh=sxsh.sweep_mesh(),
        match_fn=plan.match_fn,
    )
    carry, seen = grid.init(), set()
    for _ in range(-(-plan.num_rounds // 16)):
        carry, done = grid.runner(carry, grid.batch)
        done = np.asarray(grid.gather(done))
        st = grid.gather(carry)
        finished = np.all(
            np.asarray(st.task_finish) <= np.asarray(st.t)[..., None], axis=-1)
        np.testing.assert_array_equal(done, finished)
        assert done.shape == (len(SMALL["loads"]), SMALL["num_seeds"])
        seen.add(int(done.sum()))
        if done.all():
            break
    assert done.all() and 0 in seen and len(seen) > 2


def test_fig2_uneven_grid_shapes():
    """15 points on any device count: outputs keep the [L, S] shape and
    carry no pad rows."""
    _, sharded = _fig2_pair("megha")
    assert sharded["p50"].shape == (5, 3)
    assert np.all(np.isfinite(np.asarray(sharded["mean_util"])))


def test_sweep_mesh_validation():
    mesh = sxsh.sweep_mesh()
    assert mesh.axis_names == (sxsh.GRID_AXIS,)
    assert int(mesh.devices.size) == N_DEV
    assert int(sxsh.sweep_mesh(1).devices.size) == 1
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        sxsh.sweep_mesh(0)
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        sxsh.sweep_mesh(N_DEV + 1)


def test_pad_batch():
    import jax.numpy as jnp

    tree = {"a": jnp.arange(5, dtype=jnp.float32),
            "b": jnp.arange(10, dtype=jnp.int32).reshape(5, 2)}
    padded, n = sxsh.pad_batch(tree, 5, 4)
    assert n == 8
    np.testing.assert_array_equal(
        np.asarray(padded["a"]), [0, 1, 2, 3, 4, 4, 4, 4]
    )
    np.testing.assert_array_equal(np.asarray(padded["b"][5:]), [[8, 9]] * 3)
    same, n_same = sxsh.pad_batch(tree, 5, 5)
    assert n_same == 5 and same is tree
    with pytest.raises(ValueError):
        sxsh.pad_batch(tree, 0, 4)


def test_unknown_rule_raises():
    plan = sxs.fig2_plan("megha", **FIG2)
    with pytest.raises(ValueError, match="simx backend implements"):
        sxsh.sharded_sweep_grid(
            "nosuchrule", plan.cfg, plan.tasks, plan.submit_grid,
            plan.job_submit_grid, plan.seeds, plan.num_rounds,
        )


def _mk_arrivals(load):
    demand = 8.0  # fixed_job_factory(8, 1.0): 8 task-seconds per job
    return PoissonArrivals(
        rate=load * STEADY_W / demand,
        job_factory=fixed_job_factory(8, 1.0),
        seed=7, num_jobs=24,
    )


@pytest.mark.parametrize("rule", sorted(RULES))
def test_steady_state_parity(rule):
    """The lane-batched driver reproduces the serial streaming driver
    lane-for-lane: sketch estimates, exact retired delays, counters."""
    serial = [
        run_steady_state(rule, _mk_arrivals(ld), STEADY_W, **STEADY)
        for ld in STEADY_LOADS
    ]
    batched = sxsh.sharded_steady_state(
        rule, [_mk_arrivals(ld) for ld in STEADY_LOADS], STEADY_W,
        mesh=sxsh.sweep_mesh(min(N_DEV, len(STEADY_LOADS))), **STEADY,
    )
    assert len(batched) == len(serial)
    for ser, bat in zip(serial, batched):
        assert bat.tasks_admitted == ser.tasks_admitted
        assert bat.tasks_completed == ser.tasks_completed
        assert bat.rounds == ser.rounds
        _close(bat.quantile_estimates, ser.quantile_estimates)
        _close(np.sort(bat.delays), np.sort(ser.delays))


def test_sweep_grid_donation_parity():
    """``donate=True`` changes buffer lifetimes, never numbers — a fresh
    plan per run because donation consumes the grid inputs."""
    base = sxs.fig2_plan("megha", **FIG2)
    kept = sxs.sweep_grid(
        base.name, base.cfg, base.tasks, base.submit_grid,
        base.job_submit_grid, base.seeds, base.num_rounds,
        match_fn=base.match_fn, pick_fn=base.pick_fn, donate=False,
    )
    plan = sxs.fig2_plan("megha", **FIG2)
    donated = sxs.sweep_grid(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, plan.num_rounds,
        match_fn=plan.match_fn, pick_fn=plan.pick_fn, donate=True,
    )
    for key in kept:
        _close(donated[key], kept[key], err_msg=key)


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_compile_cache_knob(env_set, tmp_path, monkeypatch):
    """`repro.compile_cache.enable_compile_cache` leaves a cache directory
    placed by ``$JAX_COMPILATION_CACHE_DIR`` alone, and otherwise resolves
    the same absolute in-checkout directory from any working directory;
    either way it zeroes the size/time admission thresholds."""
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache

    from repro.compile_cache import enable_compile_cache

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        if env_set:
            # JAX reads the variable itself when it starts; the helper must
            # not override what the environment placed
            env_dir = str(tmp_path / "from_env")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
            jax.config.update("jax_compilation_cache_dir", env_dir)
            assert enable_compile_cache() == env_dir
            assert jax.config.jax_compilation_cache_dir == env_dir
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(
                Path(__file__).resolve().parents[1] / ".jax_compile_cache"
            )
            seen = []
            for cwd in (tmp_path, Path(__file__).parent):
                monkeypatch.chdir(cwd)
                seen.append(enable_compile_cache())
            assert seen == [want, want]
            assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        # the knob is process-global — leaked on, every later compile in
        # this process would read and write the test's cache directory
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
