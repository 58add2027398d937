"""Grid driver: the paper's Fig. 2 (load x seed) grid as one program over
the cell's chips, every grid point a simulated datacenter.

The configuration's trace block lists its ``loads``: one trace a load,
the same jobs and durations at every load with only the arrival times
moved (as ``sweep.make_load_grid`` builds them), generated for the
program's worker count as ``sweep.fig2_plan`` does.  The traffic file
says how many scheduler seeds each load runs; the columns are ``seed +
j`` (mod 2**31 - 1) for the run's ``--seed``, as a ``fig2_sweep`` user
varies them.

The program is the sharded grid's chunk runner (``shard.fig2_grid``): the
points laid over the chips, vmapped over each chip's slice under
``jax.pmap``, ``chunk`` rounds a call, one done flag a point.  Set-up,
the window and its dispatch ahead are ``fixed``'s: set-up compiles the
runner outside the persistent cache and advances fresh states by
``start_rounds``.  A replay ends once every point has finished its trace;
the chunks sent past it are dropped and the next chunk starts the whole
grid again from fresh states.

``verify`` checks every point, so that a fault of the pad, the gather or
a point's key shows: ``check.check_state`` on each point of every replay
and of the state at the window's close, and ``ref_state_gap`` of each
point at the first chunk boundary at or past ``reference_rounds``
against ``ref_megha.simulate`` on its load's trace and its scheduler
seed.  Each number is the worst over the points.
"""

from __future__ import annotations

import importlib
import os
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import check
from drivers import fixed

#: The host spans around each unit of work, which name idle gaps.
UNIT_SPANS = fixed.UNIT_SPANS

#: The state a point is checked on.
FIELDS = ("t", "rnd", "task_finish", "worker_finish", "worker_task", "lost",
          "messages", "inconsistencies")


class Driver(fixed.Driver):

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 program_cluster: dict | None = None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from repro.core.megha import grid_workers
        from repro.simx import runtime, shard
        from repro.simx.state import SimxConfig, TaskArrays

        import tracegen

        fig2_grid = shard.fig2_grid  # a program without it fails here
        self._jax = jax
        cl = dict(config["cluster"])
        self.dt, self.hop = cl["dt"], cl["hop"]
        self.hops = config["guarantees"]["launch_hops"]
        self.rule = runtime.get_rule(traffic["rule"])
        self.chunk = int(traffic["chunk"])
        self.start_rounds = int(traffic["start_rounds"])
        prog = dict(program_cluster or cl)
        if self.rule.needs_grid:
            prog["num_workers"] = grid_workers(
                prog["num_workers"], prog["num_gms"], prog["num_lms"])
        self.num_workers = prog["num_workers"]
        trace = dict(config["trace"])
        self.loads = trace.pop("loads")
        self.traces = [tracegen.generate({**trace, "load": load},
                                         self.num_workers, seed)
                       for load in self.loads]
        self.seeds = [(seed + j) % (2**31 - 1)
                      for j in range(int(traffic["seeds_per_load"]))]
        self.datacenters = len(self.loads) * len(self.seeds)
        self.prefix = int(traffic.get("reference_rounds", 0))
        self.reference = (importlib.import_module(f"ref_{traffic['rule']}")
                          if self.prefix else None)
        self.cluster = {**cl, "num_workers": self.num_workers}  # as stated
        first = self.traces[0]
        tasks = TaskArrays(**{k: jnp.asarray(v) for k, v in first.items()})
        self.grid = fig2_grid(
            self.rule.name, SimxConfig(**prog), tasks,
            np.stack([t["submit"] for t in self.traces]),
            np.stack([t["job_submit"] for t in self.traces]),
            np.asarray(self.seeds, np.int32),
            chunk=self.chunk, mesh=Mesh(np.asarray(devices), (shard.GRID_AXIS,)),
            match_fn=runtime.default_match_fn(),
            pick_fn=runtime.default_match_fn(block_rows=1),
        )
        self.runner = lambda carry: self.grid.runner(carry, self.grid.batch)
        self.ahead = 1
        self.unit_s = None
        self.inflight: deque = deque()
        self.retired = 0
        self.finished: list[tuple[object, int]] = []
        self.kept = None
        self.work: dict = {}

    def _fresh(self) -> None:
        self.state = self.grid.init()
        self.rounds = 0
        self.sent, self.sent_rounds = self.state, 0

    def _retire(self) -> None:
        """Wait for the oldest chunk in flight and count its rounds; once
        every point has finished its trace, drop the chunks sent after it
        and start the grid again."""
        jax = self._jax
        state, done, rounds = self.inflight.popleft()
        with jax.profiler.TraceAnnotation("wait"):
            completed = bool(self._points(done).all())
        self.state, self.rounds = state, rounds
        self.retired += self.chunk
        if rounds >= self.prefix:
            self._keep()
        if completed:
            with jax.profiler.TraceAnnotation("reinit"):
                self._keep()
                self.finished.append((state, rounds))
                self.inflight.clear()
                self._fresh()

    def _points(self, x) -> np.ndarray:
        """A ``[chips, per chip, ...]`` array on the host, one row a real
        point (load-major), the pad points left out."""
        x = np.asarray(x)
        return x.reshape((-1,) + x.shape[2:])[:self.datacenters]

    def _snapshot(self, state) -> list[dict]:
        arrays = {k: self._points(getattr(state, k)) for k in FIELDS}
        return [{k: v[i] for k, v in arrays.items()}
                for i in range(self.datacenters)]

    def verify(self) -> tuple[dict, int, int]:
        """The reference's numbers, worst over every point of every replay
        the window finished and of the state at its close; with the tasks
        attempted and failed, summed over the points.  The device states
        are copied to the host and freed before the reference runs."""
        snaps = [(self._snapshot(state), rounds)
                 for state, rounds in self.finished + [(self.state, self.rounds)]]
        state, rounds = self.kept or (self.state, self.rounds)
        kept = (self._snapshot(state), rounds)
        self.release()
        S = len(self.seeds)
        worst: dict[str, float] = {}
        if self.reference is not None:
            got, rounds = kept

            def simulate(i):
                return self.reference.simulate(
                    self.traces[i // S], self.cluster, seed=self.seeds[i % S],
                    rounds=rounds)[rounds]

            # numpy gives up the GIL in the wide steps
            with ThreadPoolExecutor(min(len(got), os.cpu_count() or 1)) as ex:
                refs = list(ex.map(simulate, range(len(got))))
            self.work = {"rounds": rounds}
            for i, (point, ref) in enumerate(zip(got, refs)):
                load = i // S
                trace = self.traces[load]
                worst["ref_state_gap"] = max(worst.get("ref_state_gap", 0.0),
                                             check.state_gap(point, ref))
                w = self.work.setdefault(str(self.loads[load]), {})
                for k, v in {**check.done(point, trace), **ref["work"]}.items():
                    w.setdefault(k, []).append(v)
        attempted = failed = 0
        for points, rounds in snaps:
            for i, s in enumerate(points):
                trace = self.traces[i // S]
                nums = check.check_state(
                    s, trace, rounds=rounds, dt=self.dt, hop=self.hop,
                    hops=self.hops, num_workers=self.num_workers,
                )
                for k, v in nums.items():
                    worst[k] = max(worst.get(k, 0.0), v)
                attempted += check.arrived(trace, float(s["t"]))
                failed += int(nums["ledger_gap"] + nums["timing_errors"])
        return worst, attempted, min(failed, attempted)

    def release(self) -> None:
        self.state = self.sent = self.finished = self.kept = None
        self.inflight.clear()
        self.grid = self.runner = None
