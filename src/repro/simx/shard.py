"""Mesh-sharded sweep drivers: grid batch axes laid across a device mesh.

The Fig. 2 / Fig. 4 grids and the steady-state load sweep are pure data
parallelism — the same compiled round-stage scan over different submit
arrays, seeds, fault schedules, or arrival streams, with no cross-point
communication until the final host gather (each point reduces to its own
``point_summary`` scalars *inside* the program).  ``repro.simx.sweep``
runs those batch axes serially on one device; this module lays them
across a 1-D ``"grid"`` mesh axis instead:

  * ``sweep_mesh(n_devices)`` builds the mesh (a function, never a
    module-level constant, so importing this module never touches jax
    device state).
  * ``ShardedGrid`` is the one grid program: it flattens the grid's
    axes to one batch axis, pads it to a device multiple, and runs the
    vmapped per-point round scan under ``jax.pmap`` over the mesh's
    devices, ``chunk`` rounds a call (the ``simx_chunk`` runner, with a
    done flag per point), so a grid can be timed, checked and stopped
    between chunks.  Each device runs the plain vmapped program over its
    local batch slice, closed-over structural arrays are replicated, and
    no collective appears in the compiled program.
  * ``fig2_grid`` builds it for the (load x seed) grid;
    ``sharded_sweep_grid`` / ``sharded_fig2_sweep`` run that to the round
    budget in chunks and reduce every point in-program;
    ``sharded_fig4_sweep`` gives the (severity x seed) fault grids the
    same treatment over the ``FaultSchedule`` leaves.
  * ``sharded_steady_state`` batches ``stream.run_steady_state``'s load
    axis: one ring-buffer window per offered load, the jitted segment
    vmapped over the [L]-stacked windows (their layout pytrees stack
    because every lane shares one ``SimxConfig``, so the static layout
    capacities agree), per-lane host refills between segments, and the
    lane axis sharded across the mesh — a whole tail-latency-vs-load
    curve as one mesh-parallel program.

**The executor.**  The drivers call ``jax.pmap``.  On JAX 0.9
(``jax_pmap_shmap_merge`` on by default) ``pmap`` is built on
``shard_map``: it lowers to one SPMD program partitioned over the mesh's
devices (``num_partitions`` = device count, no collective), each
partition running the vmapped point function over its batch slice.  On
four TPU v5e chips and on forced CPU devices it reproduces the serial
grids — p50/p95 at rtol 1e-5, counts exactly — including the
seed-sensitive fault grid, so every point keeps its own PRNG key.
Under jax 0.4.37 the alternatives failed on forced CPU devices: a
``NamedSharding``-constrained jit made GSPMD insert an AllGather whose
CPU rendezvous deadlocked, and a hand-written ``shard_map`` broadcast
shard 0's loop-invariant PRNG key to every device (silent on fixed-seed
grids).  ``tests/test_simx_shard.py::test_fault_grid_is_seed_sensitive``
keeps guarding that bug class.

**Pad-and-mask semantics.**  A batch of B real points is padded to the
next device multiple by repeating the last real point; the pad points
run like any other, but every per-point observable is reduced within its
own point, so the pads cannot contaminate real outputs — the host
simply slices them off after the gather.  Uneven grids therefore return
numbers identical to the single-device drivers (pinned by
``tests/test_simx_shard.py``, including a 5 x 3 grid on 8 devices).

Everything here is testable without a TPU: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set before the
first jax import — device count is fixed at backend init) and the CPU
"devices" exercise the identical partitioning.  Recipe:
docs/sharded_sweeps.md.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.simx import runtime, spans
from repro.simx import stream as _stream
from repro.simx import sweep as _sweep
from repro.simx import telemetry as tlm
from repro.simx.faults import FaultSchedule
from repro.simx.runtime import MatchFn
from repro.simx.state import SimxConfig, TaskArrays, spec
from repro.workload.synth import ArrivalProcess

#: The one mesh axis every sharded driver uses: the flattened batch of
#: grid points (or steady-state lanes).
GRID_AXIS = "grid"
#: Rounds per call of the chunk runner when a grid runs to its whole
#: round budget (``engine.run_to_completion``'s default chunk).
DEFAULT_CHUNK = 256


def sweep_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D device mesh over the first ``n_devices`` devices (default:
    all), axis name ``"grid"`` — the batch axis of every sharded driver.

    A function, not a module constant: importing this module never
    touches jax device state, and tests force a CPU device count via
    ``XLA_FLAGS`` before the first jax call."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"sweep_mesh(n_devices={n_devices}): host has {len(devs)} "
            "device(s); need 1 <= n_devices <= that "
            "(XLA_FLAGS=--xla_force_host_platform_device_count=N forces "
            "more CPU devices, before the first jax import)"
        )
    return Mesh(np.asarray(devs[:n]), (GRID_AXIS,))


def grid_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis-over-``"grid"`` NamedSharding (trailing dims
    replicated) — the layout of every batched arg and result."""
    return NamedSharding(mesh, P(GRID_AXIS))


def pad_batch(tree, n_real: int, multiple: int):
    """Pad every leaf's leading batch axis from ``n_real`` up to the next
    multiple of ``multiple`` by repeating the last real entry.  Returns
    ``(padded_tree, n_padded)``.  Pad entries are real computations whose
    outputs the caller slices off (``[:n_real]``) after the gather —
    per-point reductions mean they cannot affect the real points."""
    if multiple < 1 or n_real < 1:
        raise ValueError("pad_batch needs n_real >= 1 and multiple >= 1")
    n_pad = -(-n_real // multiple) * multiple
    if n_pad == n_real:
        return tree, n_real

    def pad(x):
        reps = jnp.broadcast_to(
            x[n_real - 1 : n_real], (n_pad - n_real,) + x.shape[1:]
        )
        return jnp.concatenate([x, reps], axis=0)

    return jax.tree.map(pad, tree), n_pad


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GridShard:
    """The flattened (row x col) batch of Fig. 2 grid points — the one
    traced argument of a sharded grid program.  B is the padded batch
    size (a device multiple); entry ``b = i * cols + j`` carries row
    (load) i and column (seed) j."""

    submit: jax.Array = spec("float32[B, T]")
    job_submit: jax.Array = spec("float32[B, J]")
    seed: jax.Array = spec("int32[B]")


def make_grid_shard(
    submit_grid: jax.Array,
    job_submit_grid: jax.Array,
    seeds: jax.Array,
) -> tuple[GridShard, int, int]:
    """Flatten (load x seed) inputs to one batch axis: returns
    ``(GridShard with B = rows * cols, rows, cols)`` — row-major, so the
    host reshape ``[:B].reshape(rows, cols)`` restores the grid."""
    submit_grid = jnp.asarray(submit_grid)
    job_submit_grid = jnp.asarray(job_submit_grid)
    seeds = jnp.asarray(seeds, jnp.int32)
    rows, cols = int(submit_grid.shape[0]), int(seeds.shape[0])
    return (
        GridShard(
            submit=jnp.repeat(submit_grid, cols, axis=0),
            job_submit=jnp.repeat(job_submit_grid, cols, axis=0),
            seed=jnp.tile(seeds, rows),
        ),
        rows,
        cols,
    )


class ShardedGrid:
    """A (row x col) grid of simulated datacenters laid over ``mesh`` as
    one chunked program.

    ``point(p)`` turns one entry of ``batch`` (a pytree with a leading
    ``rows * cols`` axis, row-major) into ``(tasks, seed, faults)``.  The
    batch is padded to a device multiple, laid out ``[n_dev, per_dev,
    ...]`` and placed on the mesh once; every program below is the
    per-point function vmapped over a device's slice and run under
    ``jax.pmap`` (the module docstring describes the executor):

      * ``init()`` builds every point's fresh carry (``simx.build`` span);
      * ``runner(carry, batch)`` advances every point by ``chunk`` rounds
        and returns ``(carry, done)``, ``done`` one flag a point, reduced
        inside the chunk (``simx.done``) as ``engine.make_chunk_runner``
        does.  It is the ``spans.Program`` named ``simx_chunk``; each
        call rebuilds the points' steps from their own inputs, so every
        point keeps its own PRNG key;
      * ``run(carry, n)`` advances exactly ``n`` rounds: whole chunks,
        then one remainder program;
      * ``summary(carry)`` reduces each point to ``sweep.point_summary``.

    ``gather`` brings a ``[n_dev, per_dev, ...]`` result back as ``[rows,
    cols, ...]`` with the pad points sliced off.  Chunks compose: ``k``
    chunks give the state ``k * chunk`` rounds of one scan give."""

    def __init__(
        self,
        scheduler: str,
        cfg: SimxConfig,
        point: Callable,
        batch,
        rows: int,
        cols: int,
        mesh: Mesh,
        *,
        chunk: int,
        match_fn: MatchFn | None = None,
        pick_fn: MatchFn | None = None,
        provenance: bool = False,
    ):
        name = scheduler.lower()
        rule = runtime.get_rule(name)  # fail fast on unknown schedulers
        if chunk < 1:
            raise ValueError(f"ShardedGrid needs chunk >= 1, not {chunk}")
        self.rows, self.cols, self.n_real = rows, cols, rows * cols
        self.n_dev = int(mesh.devices.size)
        batch, n_padded = pad_batch(batch, self.n_real, self.n_dev)
        self.per_dev = n_padded // self.n_dev
        self.batch = jax.device_put(
            jax.tree.map(
                lambda x: jnp.reshape(
                    x, (self.n_dev, self.per_dev) + x.shape[1:]
                ),
                batch,
            ),
            grid_sharding(mesh),
        )
        self.chunk = int(chunk)
        devices = list(mesh.devices.reshape(-1))

        def sharded(fn):
            return jax.pmap(jax.vmap(fn), axis_name=GRID_AXIS, devices=devices)

        def fresh(p):
            tasks, _, _ = point(p)
            return runtime.init_carry(name, cfg, tasks, provenance=provenance)

        def advance(n):
            def simx_chunk(carry, p):
                tasks, seed, faults = point(p)
                step = runtime.point_step(
                    name, cfg, tasks, seed, match_fn=match_fn,
                    pick_fn=pick_fn, faults=faults, provenance=provenance,
                )
                carry = runtime.scan_rounds(step, carry, n)
                s = runtime.carry_state(carry)
                with jax.named_scope("simx.done"):
                    done = jnp.all(s.task_finish <= s.t)
                return carry, done

            return sharded(simx_chunk)

        def summarize(carry, p):
            tasks, _, _ = point(p)
            state, prov = carry if provenance else (carry, None)
            return _sweep.point_summary(
                state, tasks, has_queues=rule.has_queues, provenance=prov,
                dt=cfg.dt,
            )

        self._init = sharded(fresh)
        self._advance = functools.lru_cache(maxsize=None)(advance)
        self.runner = spans.Program("simx_chunk", self._advance(self.chunk))
        self._summary = sharded(summarize)

    def init(self):
        """Every point's fresh carry, ``[n_dev, per_dev, ...]`` on the
        mesh."""
        with spans.span("simx.build"):
            return self._init(self.batch)

    def run(self, carry, num_rounds: int):
        """Advance every point by exactly ``num_rounds`` rounds."""
        runtime.check_round_budget(num_rounds, "ShardedGrid.run(num_rounds=...)")
        whole, rest = divmod(int(num_rounds), self.chunk)
        for _ in range(whole):
            carry, _ = self.runner(carry, self.batch)
        if rest:
            carry, _ = self._advance(rest)(carry, self.batch)
        return carry

    def summary(self, carry) -> dict[str, jax.Array]:
        """``sweep.point_summary`` of every point, ``[rows, cols]``."""
        return self.gather(self._summary(carry, self.batch))

    def gather(self, tree):
        """A ``[n_dev, per_dev, ...]`` result as ``[rows, cols, ...]``,
        the pad points sliced off."""
        n = self.n_dev * self.per_dev
        return jax.tree.map(
            lambda v: jnp.reshape(
                jnp.reshape(v, (n,) + v.shape[2:])[: self.n_real],
                (self.rows, self.cols) + v.shape[2:],
            ),
            tree,
        )


def fig2_grid(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    submit_grid: jax.Array,      # float32[L, T]
    job_submit_grid: jax.Array,  # float32[L, J]
    seeds: jax.Array,            # int[S]
    *,
    chunk: int,
    mesh: Optional[Mesh] = None,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    provenance: bool = False,
) -> ShardedGrid:
    """The chunked mesh-sharded (load x seed) grid: point ``i * S + j``
    replays ``tasks`` with load ``i``'s submit times under scheduler seed
    ``seeds[j]``."""
    flat, rows, cols = make_grid_shard(submit_grid, job_submit_grid, seeds)

    def point(g: GridShard):
        tk = dataclasses.replace(tasks, submit=g.submit, job_submit=g.job_submit)
        return tk, g.seed, None

    return ShardedGrid(
        scheduler, cfg, point, flat, rows, cols,
        sweep_mesh() if mesh is None else mesh, chunk=chunk,
        match_fn=match_fn, pick_fn=pick_fn, provenance=provenance,
    )


def sharded_grid_program(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    submit_grid: jax.Array,      # float32[L, T]
    job_submit_grid: jax.Array,  # float32[L, J]
    seeds: jax.Array,            # int[S]
    num_rounds: int,
    *,
    mesh: Optional[Mesh] = None,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    provenance: bool = False,
) -> Callable[[], dict]:
    """Build (without running) the mesh-sharded (load x seed) grid
    program: a ``fig2_grid`` run from fresh states to exactly
    ``num_rounds`` in ``DEFAULT_CHUNK``-round calls.  Returns a zero-arg
    runner producing the same ``[L, S]`` summary dict as ``sweep_grid``."""
    grid = fig2_grid(
        scheduler, cfg, tasks, submit_grid, job_submit_grid, seeds,
        chunk=DEFAULT_CHUNK, mesh=mesh, match_fn=match_fn, pick_fn=pick_fn,
        provenance=provenance,
    )
    return lambda: grid.summary(grid.run(grid.init(), num_rounds))


def sharded_sweep_grid(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    submit_grid: jax.Array,
    job_submit_grid: jax.Array,
    seeds: jax.Array,
    num_rounds: int,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    provenance: bool = False,
    mesh: Optional[Mesh] = None,
) -> dict[str, jax.Array]:
    """Drop-in mesh-parallel ``sweep.sweep_grid``: identical signature
    plus ``mesh`` (default: all devices), identical ``[L, S]`` outputs —
    the batch is padded to a device multiple and the pad points sliced
    off on the host, so uneven grids return the same numbers."""
    return sharded_grid_program(
        scheduler, cfg, tasks, submit_grid, job_submit_grid, seeds,
        num_rounds, mesh=mesh, match_fn=match_fn, pick_fn=pick_fn,
        provenance=provenance,
    )()


def sharded_fault_program(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    schedules: FaultSchedule,    # leaves carry a leading severity axis [F]
    seeds: jax.Array,            # int[S]
    num_rounds: int,
    *,
    mesh: Optional[Mesh] = None,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
) -> Callable[[], dict]:
    """The Fig. 4 counterpart of ``sharded_grid_program``: the flattened
    (severity x seed) axis across the mesh, ``FaultSchedule`` leaves
    repeated per seed along the batch axis."""
    seeds = jnp.asarray(seeds, jnp.int32)
    rows = int(jax.tree_util.tree_leaves(schedules)[0].shape[0])
    cols = int(seeds.shape[0])
    batch = (
        jax.tree.map(lambda x: jnp.repeat(x, cols, axis=0), schedules),
        jnp.tile(seeds, rows),
    )

    def point(p):
        fs, seed = p
        return tasks, seed, fs

    grid = ShardedGrid(
        scheduler, cfg, point, batch, rows, cols,
        sweep_mesh() if mesh is None else mesh, chunk=DEFAULT_CHUNK,
        match_fn=match_fn, pick_fn=pick_fn,
    )
    return lambda: grid.summary(grid.run(grid.init(), num_rounds))


def sharded_fault_sweep_grid(
    scheduler: str,
    cfg: SimxConfig,
    tasks: TaskArrays,
    schedules: FaultSchedule,
    seeds: jax.Array,
    num_rounds: int,
    match_fn: MatchFn | None = None,
    pick_fn: MatchFn | None = None,
    mesh: Optional[Mesh] = None,
) -> dict[str, jax.Array]:
    """Drop-in mesh-parallel ``sweep.fault_sweep_grid`` (same ``[F, S]``
    outputs; see ``sharded_sweep_grid`` for the pad/mask contract)."""
    return sharded_fault_program(
        scheduler, cfg, tasks, schedules, seeds, num_rounds,
        mesh=mesh, match_fn=match_fn, pick_fn=pick_fn,
    )()


def sharded_fig2_sweep(
    scheduler: str, *, mesh: Optional[Mesh] = None, **kw
) -> dict[str, np.ndarray]:
    """Mesh-parallel ``sweep.fig2_sweep``: same keywords, same grid
    construction (one shared ``fig2_plan``), the (load x seed) batch
    sharded across ``mesh``.  Adds ``n_devices`` to the result."""
    plan = _sweep.fig2_plan(scheduler, **kw)
    mesh = sweep_mesh() if mesh is None else mesh
    out = sharded_grid_program(
        plan.name, plan.cfg, plan.tasks, plan.submit_grid,
        plan.job_submit_grid, plan.seeds, plan.num_rounds, mesh=mesh,
        match_fn=plan.match_fn, pick_fn=plan.pick_fn,
        provenance=plan.provenance,
    )()
    res = {k: np.asarray(v) for k, v in out.items()}
    res.update(plan.annotate)
    res["n_devices"] = np.asarray(int(mesh.devices.size))
    return res


def sharded_fig4_sweep(
    scheduler: str, *, mesh: Optional[Mesh] = None, **kw
) -> dict[str, np.ndarray]:
    """Mesh-parallel ``sweep.fig4_sweep``: same keywords, same schedule
    construction (one shared ``fig4_plan``), the (severity x seed) batch
    sharded across ``mesh``.  Adds ``n_devices`` to the result."""
    plan = _sweep.fig4_plan(scheduler, **kw)
    mesh = sweep_mesh() if mesh is None else mesh
    out = sharded_fault_program(
        plan.name, plan.cfg, plan.tasks, plan.schedules, plan.seeds,
        plan.num_rounds, mesh=mesh,
        match_fn=plan.match_fn, pick_fn=plan.pick_fn,
    )()
    res = {k: np.asarray(v) for k, v in out.items()}
    res.update(plan.annotate)
    res["n_devices"] = np.asarray(int(mesh.devices.size))
    return res


# ---------------------------------------------------------------------------
# the sharded steady-state driver (ROADMAP item 2a + mesh)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _batched_segment(
    rule: str, cfg: SimxConfig, num_rounds: int, mesh: Mesh
) -> Callable:
    """The lane-batched streaming segment: ``stream``'s segment core
    vmapped over each device's local lane slice and run under
    ``jax.pmap`` over the mesh's devices — every batched arg (state,
    window tasks, layout, sketch) arrives as ``[n_dev, per_dev, ...]``,
    each device advances its local lanes, and no collective appears in
    the compiled program (the module docstring describes the executor).
    Memoized like ``stream._default_segment`` — every refill, and every
    same-shaped sweep, reuses one compilation.  Lanes must share one
    ``SimxConfig`` (the layouts' static capacities then agree, which is
    what lets the layout pytrees stack)."""
    core = _stream._segment_core(
        rule, cfg, jax.random.PRNGKey(cfg.seed), num_rounds, None, None
    )
    seg = jax.pmap(
        jax.vmap(core), axis_name=GRID_AXIS,
        devices=list(mesh.devices.reshape(-1)),
    )
    return seg


def _stack_lanes(trees):
    """Stack per-lane pytrees along a new leading lane axis (static
    metadata — layout capacities — must agree, i.e. one shared cfg)."""
    if trees[0] is None:
        return None
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _lane(tree, i: int):
    """Slice lane ``i`` back out of a stacked result."""
    return jax.tree.map(lambda x: x[i], tree)


def _to_mesh(tree, n_dev: int):
    """Fold a ``[L_pad, ...]`` lane-stacked pytree to pmap's
    ``[n_dev, L_pad // n_dev, ...]`` layout (``None`` passes through)."""
    if tree is None:
        return None
    return jax.tree.map(
        lambda x: jnp.reshape(x, (n_dev, x.shape[0] // n_dev) + x.shape[1:]),
        tree,
    )


def _from_mesh(tree):
    """Flatten pmap's ``[n_dev, per_dev, ...]`` output back to the
    ``[L_pad, ...]`` lane-stacked layout."""
    if tree is None:
        return None
    return jax.tree.map(
        lambda x: jnp.reshape(x, (x.shape[0] * x.shape[1],) + x.shape[2:]),
        tree,
    )


def sharded_steady_state(
    rule: str,
    arrivals: Sequence[ArrivalProcess],
    num_workers: int,
    *,
    mesh: Optional[Mesh] = None,
    window_jobs: int = 256,
    window_tasks: Optional[int] = None,
    rounds_per_refill: int = 64,
    horizon: Optional[float] = None,
    max_rounds: int = 2_000_000,
    quantiles: tuple = tlm.DEFAULT_QUANTILES,
    collect_delays: bool = True,
    num_gms: int = 8,
    num_lms: int = 8,
    dt: float = 0.05,
    seed: int = 0,
    **cfg_kw,
) -> list[_stream.SteadyRun]:
    """Run one streaming steady-state lane per arrival process — a whole
    tail-latency-vs-offered-load curve — as one mesh-parallel program.

    Each lane gets its own ring-buffer window over one shared
    ``SimxConfig`` (same capacities => the per-rule layout pytrees stack);
    every segment advances all lanes at once through the lane-vmapped
    jitted segment with the lane axis sharded across ``mesh``, then each
    live lane refills on the host exactly like ``run_steady_state``.  A
    lane that drains (or trips ``horizon``/``max_rounds``) is frozen: its
    state/sketch stop updating while the remaining lanes run on (the
    frozen lane still occupies its mesh slot, like a pad point).  The
    lane count is padded to a device multiple by repeating lane 0; pad
    lanes are dropped before returning.

    Returns one ``stream.SteadyRun`` per lane, in ``arrivals`` order,
    matching the serial driver's observables (quantile estimates, exact
    retired delays, gauge series, conservation stats).  Telemetry and
    provenance are not supported on this batched path — use the serial
    ``run_steady_state`` for those.
    """
    name = rule.lower()
    r = runtime.get_rule(name)
    runtime.check_round_budget(max_rounds, "sharded_steady_state(max_rounds=...)")
    mesh = sweep_mesh() if mesh is None else mesh
    arrivals = list(arrivals)
    if not arrivals:
        raise ValueError("sharded_steady_state needs at least one lane")
    if window_tasks is None:
        window_tasks = window_jobs * 16
    cfg = _stream.stream_config(
        name, num_workers, window_tasks=window_tasks,
        num_gms=num_gms, num_lms=num_lms, dt=dt, seed=seed, **cfg_kw,
    )
    lanes = len(arrivals)
    n_dev = int(mesh.devices.size)
    n_pad = -(-lanes // n_dev) * n_dev
    wins = [
        _stream._StreamWindow(
            a, cfg, name, window_jobs, window_tasks, cfg.seed
        )
        for a in arrivals
    ]
    lane_state = [r.init(cfg, w.tasks()) for w in wins]
    lane_sketch = [tlm.sketch_init(quantiles) for _ in wins]
    lane_done = [False] * lanes
    lane_rounds = [0] * lanes
    series_keys = (
        "t", "utilization", "busy_util", "pending", "running",
        "window_jobs", "admission_lag",
    )
    lane_series: list[dict] = [
        {**{k: [] for k in series_keys}, **{f"q{q}": [] for q in quantiles}}
        for _ in wins
    ]
    lane_refills: list[list] = [[] for _ in wins]
    seg = _batched_segment(name, cfg, int(rounds_per_refill), mesh)

    def padded(items: list) -> list:
        return items + [items[0]] * (n_pad - lanes)

    while not all(lane_done):
        carry = _to_mesh(_stack_lanes(padded(lane_state)), n_dev)
        tasks_b = _to_mesh(_stack_lanes(padded([w.tasks() for w in wins])), n_dev)
        layout_b = _to_mesh(_stack_lanes(padded([w.layout() for w in wins])), n_dev)
        sketch_b = _to_mesh(_stack_lanes(padded(lane_sketch)), n_dev)
        carry, sketch_b, gauges, _blocks = seg(carry, tasks_b, layout_b, sketch_b)
        carry = _from_mesh(carry)
        sketch_b = _from_mesh(sketch_b)
        gauges = _from_mesh(gauges)
        for i in range(lanes):
            if lane_done[i]:
                continue
            state = _lane(carry, i)
            lane_sketch[i] = _lane(sketch_b, i)
            lane_rounds[i] += rounds_per_refill
            lag = max(0.0, float(state.t) - wins[i].next_submit)
            state, stats, _ = wins[i].refill(state, collect_delays=collect_delays)
            lane_state[i] = state
            lane_refills[i].append(stats)
            s = lane_series[i]
            s["t"].append(stats["t"])
            s["utilization"].append(float(gauges["utilization"][i]))
            s["busy_util"].append(
                stats["busy"] / (cfg.num_workers * stats["span"])
                if stats["span"] > 0 else 0.0
            )
            s["pending"].append(int(gauges["pending"][i]))
            s["running"].append(int(gauges["running"][i]))
            s["window_jobs"].append(stats["window_jobs"])
            s["admission_lag"].append(lag)
            qs = np.asarray(tlm.sketch_quantiles(lane_sketch[i]))
            for qi, q in enumerate(quantiles):
                s[f"q{q}"].append(float(qs[qi]))
            if (
                wins[i].drained
                or (horizon is not None and float(state.t) >= horizon)
                or lane_rounds[i] >= max_rounds
            ):
                lane_done[i] = True
    runs = []
    for i in range(lanes):
        state, win = lane_state[i], wins[i]
        tf = np.asarray(state.task_finish)
        in_window_done = int(
            np.sum(
                (np.asarray(win.tasks().job) < win.J_cap - 1)
                & (tf <= float(state.t))
            )
        )
        runs.append(
            _stream.SteadyRun(
                rule=name,
                cfg=cfg,
                quantile_targets=tuple(quantiles),
                quantile_estimates=np.asarray(
                    tlm.sketch_quantiles(lane_sketch[i])
                ),
                series={k: np.asarray(v) for k, v in lane_series[i].items()},
                refills=lane_refills[i],
                delays=(
                    np.asarray(win.retired_delays, np.float64)
                    if collect_delays else None
                ),
                jobs_admitted=win.jobs_admitted,
                jobs_completed=win.jobs_retired,
                tasks_admitted=win.tasks_admitted,
                tasks_completed=win.tasks_retired + in_window_done,
                lost=int(state.lost),
                messages=int(state.messages),
                probes=int(state.probes),
                rounds=lane_rounds[i],
                end_time=float(state.t),
                state_bytes=_stream.state_nbytes(
                    state, win.tasks(), win.layout(), lane_sketch[i]
                ),
            )
        )
    return runs
