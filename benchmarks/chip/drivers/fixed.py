"""Fixed-trace driver: one datacenter replays one trace in chunks.

The program is built through the rule registry with the calls
``engine.simulate_workload`` makes, and advanced by the engine's compiled
chunk runner (``engine.make_chunk_runner``: ``chunk`` rounds, with the
done flag ``engine.run_to_completion`` reads fused into the chunk).

Set-up compiles the chunk runner and advances a fresh ``rule.init`` by the
traffic file's ``start_rounds`` (whole chunks, at least two: the first
compiles, the last is timed, ``unit_s``); the window continues from there,
so every run of a seed times the same rounds.  The program holds the
seed's trace in the chunk runner as constants, so a new seed compiles a
new runner: that compile is kept out of the persistent cache, and every
run's set-up compiles it, whatever seeds ran before.

In the window, ``send()`` dispatches one chunk and waits only for the
oldest once more than ``ahead`` are in flight, so the chip keeps working
while the host stands still; ``drain()`` waits for every chunk sent.  A
chunk's done flag is read when it is waited for: when the trace has
completed, the chunks sent after it (past the end of that replay) are
dropped and the next chunk starts the trace again from a fresh
``rule.init``.
"""

from __future__ import annotations

import contextlib
import importlib
import time
from collections import deque

import numpy as np

import check

#: The host spans around this driver's work, which name idle gaps.
UNIT_SPANS = ("chunk", "wait", "reinit")


class Driver:
    datacenters = 1

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 program_cluster: dict | None = None):
        import jax
        import jax.numpy as jnp

        from repro.core.megha import grid_workers
        from repro.simx import engine, runtime
        from repro.simx.state import SimxConfig, TaskArrays

        import tracegen

        self._jax = jax
        cl = dict(config["cluster"])
        self.dt, self.hop = cl["dt"], cl["hop"]
        self.hops = config["guarantees"]["launch_hops"]
        self.rule = runtime.get_rule(traffic["rule"])
        self.chunk = int(traffic["chunk"])
        self.start_rounds = int(traffic["start_rounds"])
        self.trace = tracegen.generate(config["trace"], cl["num_workers"],
                                       seed)
        prog = dict(program_cluster or cl)
        if self.rule.needs_grid:
            prog["num_workers"] = grid_workers(
                prog["num_workers"], prog["num_gms"], prog["num_lms"])
        self.num_workers = prog["num_workers"]
        # a traffic file that states the scheduler's seed gives every run
        # the same schedule; its ``relabel_workers`` lets ``--seed`` rename
        # megha's workers instead (``tracegen.worker_labels``)
        rule_seed = int(traffic.get("scheduler_seed", seed % (2**31 - 1)))
        self.rule_seed = rule_seed
        self.labels = (tracegen.worker_labels(seed, self.num_workers,
                                              prog["num_gms"], prog["num_lms"])
                       if traffic.get("relabel_workers") else None)
        # a rule with a plain re-simulation of its own: the reference
        # replays the window's first ``reference_rounds`` rounds
        self.prefix = int(traffic.get("reference_rounds", 0))
        self.reference = (importlib.import_module(f"ref_{traffic['rule']}")
                          if self.prefix else None)
        self.cluster = {**cl, "num_workers": self.num_workers}  # as stated
        with jax.default_device(devices[0]):
            self.tasks = TaskArrays(**{k: jnp.asarray(v)
                                       for k, v in self.trace.items()})
            self.cfg = SimxConfig(seed=rule_seed, **prog)
            key = jax.random.PRNGKey(rule_seed)
            if self.labels is None:
                step = self.rule.build_step(
                    self.cfg, self.tasks, key,
                    match_fn=runtime.default_match_fn(),
                    pick_fn=runtime.default_match_fn(block_rows=1),
                )
            else:
                from repro.simx import megha

                orders = jnp.asarray(self.labels)[megha.gm_orders(key,
                                                                  self.cfg)]
                step = megha.make_megha_step(self.cfg, self.tasks, orders,
                                             runtime.default_match_fn())
        self.step = step
        self.runner = engine.make_chunk_runner(step, self.chunk)
        self.device = devices[0]
        self.ahead = 1
        self.unit_s = None
        self.inflight: deque = deque()
        self.retired = 0
        self.finished: list[tuple[dict, int]] = []
        self.kept = None
        self.work: dict = {}

    def _fresh(self) -> None:
        with self._jax.default_device(self.device):
            self.state = self.rule.init(self.cfg, self.tasks)
        self.rounds = 0
        self.sent, self.sent_rounds = self.state, 0

    def warm(self) -> None:
        """Compile the runner outside the persistent cache, and advance a
        fresh state by ``start_rounds``, the first chunk included; the
        last chunk's wall time is ``unit_s``."""
        if self.start_rounds < 2 * self.chunk:
            raise ValueError("start_rounds must hold two chunks: one "
                             "compiles, one is timed")
        self._fresh()
        with uncached():
            self.send()
            self.drain()
        while self.rounds < self.start_rounds:
            t0 = time.perf_counter()
            self.send()
            self.drain()
            self.unit_s = time.perf_counter() - t0
        self.finished, self.kept = [], None

    def send(self) -> None:
        """Dispatch one chunk after the last one sent; wait for the oldest
        while more than ``ahead`` are in flight."""
        with self._jax.profiler.TraceAnnotation("chunk"):
            self.sent, done = self.runner(self.sent)
        self.sent_rounds += self.chunk
        self.inflight.append((self.sent, done, self.sent_rounds))
        while len(self.inflight) > self.ahead:
            self._retire()

    def drain(self) -> None:
        """Wait for every chunk sent."""
        while self.inflight:
            self._retire()

    def _retire(self) -> None:
        """Wait for the oldest chunk in flight and count its rounds; a
        completed trace drops the chunks sent after it and starts again."""
        jax = self._jax
        state, done, rounds = self.inflight.popleft()
        with jax.profiler.TraceAnnotation("wait"):
            completed = bool(done)
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

    def _keep(self) -> None:
        """Hold on to the first state of the window at or past the
        reference's ``reference_rounds`` (or a replay that finished before
        them), for the reference to compare whole; the chunk runner does
        not donate its input, so this costs no device work."""
        if self.prefix and self.kept is None:
            self.kept = (self.state, self.rounds)

    def verify(self) -> tuple[dict, int, int]:
        """The reference's numbers, worst over every replay the window
        finished and the state at its close; with the tasks attempted
        (arrived by each state's time) and failed (the ledger and timing
        breaches).  The device state is copied to the host and freed
        before the reference runs."""
        fields = ("t", "rnd", "task_finish", "worker_finish", "worker_task",
                  "lost", "messages", "inconsistencies")
        snaps = [({k: np.asarray(getattr(state, k)) for k in fields}, rounds)
                 for state, rounds in self.finished + [(self.state, self.rounds)]]
        # a window shorter than the reference's rounds: its close state
        state, rounds = self.kept or (self.state, self.rounds)
        kept = ({k: np.asarray(getattr(state, k)) for k in fields}, rounds)
        self.release()
        worst: dict[str, float] = {}
        if self.reference is not None:
            got, rounds = kept
            ref = self.reference.simulate(
                self.trace, self.cluster, seed=self.rule_seed, rounds=rounds,
                **({} if self.labels is None else {"labels": self.labels}),
            )[rounds]
            worst["ref_state_gap"] = check.state_gap(got, ref)
            self.work = {"rounds": rounds, **check.done(got, self.trace),
                         **ref["work"]}
        attempted = failed = 0
        for s, rounds in snaps:
            nums = check.check_state(
                s, self.trace, rounds=rounds, dt=self.dt, hop=self.hop,
                hops=self.hops, num_workers=self.num_workers,
            )
            for k, v in nums.items():
                worst[k] = max(worst.get(k, 0.0), v)
            attempted += check.arrived(self.trace, float(s["t"]))
            failed += int(nums["ledger_gap"] + nums["timing_errors"])
        return worst, attempted, min(failed, attempted)

    def release(self) -> None:
        self.state = self.sent = self.finished = self.kept = None
        self.inflight.clear()
        self.step = self.runner = None


@contextlib.contextmanager
def uncached():
    """JAX's persistent compilation cache off for the block."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
