#!/usr/bin/env python3
"""Run the compiled scheduler simulator end to end on a TPU chip.

    python3 chip_smoke.py [--seed N]      # one chip: the main path
    python3 chip_smoke.py --four-chips    # four chips: the sharded path only

One process does everything (a chip belongs to one process at a time), and
every input is generated from ``--seed``.  Each phase prints one line with
its sizes, counts and timings: ``wall_s``, and ``compile_s``, the part of
it XLA spent compiling (or reading the persistent cache) — information,
not benchmark results.  A failed check raises,
and the script exits non-zero without its last line.

One-chip phases, in order:

  device      refuse anything but a TPU (no CPU fallback);
  main_path   ``engine.simulate_workload`` for every registered rule on the
              paper's synthetic cluster at its largest size (50k workers,
              480 jobs x 1000 one-second tasks, load 0.8) at ``dt=0.01``,
              the step the fidelity contract certifies: every task
              completes, the final ledger balances, and the oracle's
              p50/p95 is at or below every rule's;
  chip_vs_cpu megha and sparrow at 4,096 workers on the chip and on the
              host CPU: integer counters equal exactly, p50/p95 within
              ``CPU_DELAY_ATOL``;
  pallas      the match kernel compiled (``interpret=False``) at the
              simulator's three widths, equal to the jnp reference; megha
              at full size with ``use_pallas=True`` equal to the jnp run;
  drivers     one ``fig2_sweep`` grid at 10k workers and one
              ``run_steady_state`` stream through several window refills.

``--four-chips`` runs only ``shard.sharded_fig2_sweep`` over a four-device
mesh against the serial ``fig2_sweep`` on one chip (5 loads x 3 seeds), and
the seed-sensitivity check of the sharded Fig. 4 executor.

The last line of standard output is one JSON object naming the device.
The persistent compilation cache (``repro.compile_cache``) is on, so a
second run reads what the first compiled.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: The paper's largest synthetic cluster (Fig. 2): 50k workers, jobs of
#: 1000 one-second tasks at load 0.8; 480 jobs is ``bench_simx.SWEEP_FULL``.
FULL = dict(num_jobs=480, tasks_per_job=1000, task_duration=1.0, load=0.8,
            num_workers=50_000)
#: The step size the engine's fidelity contract certifies.
DT = 0.01
#: The chip-versus-host comparison: small enough for the host CPU.
SMALL = dict(num_jobs=100, tasks_per_job=100, task_duration=1.0, load=0.8,
             num_workers=4096)
#: p50/p95 agreement between chip and host, in seconds: a tenth of a round.
#: Identical schedules differ only by float32 rounding (~1e-6 s at these
#: times); one worker seen free a round earlier or later shifts a delay by
#: a whole ``DT`` and fails it.
CPU_DELAY_ATOL = 1e-3
#: The oracle lower-bounds every rule up to round quantization: two rounds,
#: the slack ``tests/test_simx_runtime.py`` allows the same property.
ORACLE_SLACK = 2 * DT
#: Kernel widths: megha's [G, W] GM rows, the oracle's [1, W] row, and the
#: sparrow/eagle [W, R] head-of-queue pick with one-row tiles.
KERNEL_WIDTHS = ((8, 50_000, 64), (1, 50_000, 64), (50_000, 64, 1))
FIG2 = dict(loads=(0.5, 0.8), num_seeds=2, num_workers=10_000, num_jobs=20,
            tasks_per_job=1000)
STEADY_W = 10_000
STEADY_JOB_TASKS = 100
FOUR_FIG2 = dict(loads=(0.35, 0.55, 0.7, 0.85, 0.95), num_seeds=3,
                 num_workers=10_000, num_jobs=20, tasks_per_job=1000)
#: ``tests/test_simx_shard.py::test_fault_grid_is_seed_sensitive``'s grid.
FOUR_FIG4 = dict(fractions=(0.0, 0.05, 0.1), num_seeds=4, num_workers=64,
                 num_jobs=6, tasks_per_job=8, dt=0.05, num_gms=2, num_lms=2)


class _Clock:
    """XLA compile seconds and persistent-cache hits, from JAX's
    monitoring events, accumulated while a phase runs."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str, **sizes):
        """Print one line for the phase: its sizes, the counts its body
        adds to the yielded dict, and its wall and compile seconds."""
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        info: dict = {}
        t0 = time.perf_counter()
        yield info
        fields = {**sizes, **info, "wall_s": time.perf_counter() - t0,
                  "compile_s": self.compile_s - c0,
                  "cache_hits": self.hits - h0,
                  "cache_misses": self.misses - m0}
        print(f"phase={name} " + " ".join(f"{k}={v}" for k, v in fields.items()),
              flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def device():
    """The chip JAX found; anything but a TPU is refused."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); "
            "this script measures nothing on the CPU"
        )
    return dev


def run_summary(run) -> dict:
    """Counters, the final conservation ledger and p50/p95 job delay of a
    finished ``SimxRun``, read from its dense arrays.  The ledger counts
    done and pending on the task side and running on the worker side, so
    ``done + running + pending == tasks`` ties the two together."""
    import numpy as np

    s = run.state
    tf = np.asarray(s.task_finish)
    t = float(s.t)
    delays = run.job_delays()
    p50, p95 = np.percentile(delays, [50, 95])
    return dict(
        tasks=run.tasks.num_tasks, done=int((tf <= t).sum()),
        running=int((np.asarray(s.worker_finish) > t).sum()),
        pending=int(np.isinf(tf).sum()),
        lost=int(s.lost), rounds=int(s.rnd), messages=int(s.messages),
        probes=int(s.probes), inconsistencies=int(s.inconsistencies),
        jobs_unfinished=int(np.isnan(delays).sum()),
        p50=float(p50), p95=float(p95),
    )


def check_complete(name: str, m: dict) -> None:
    """Every task completed, nothing lost, and the ledger balances with
    nothing running or pending."""
    check(m["done"] + m["running"] + m["pending"] == m["tasks"],
          f"{name}: ledger does not balance: {m}")
    check(m["done"] == m["tasks"] and m["running"] == 0 and m["pending"] == 0,
          f"{name}: not every task completed: {m}")
    check(m["lost"] == 0 and m["jobs_unfinished"] == 0,
          f"{name}: lost work or unfinished jobs: {m}")


def main_path(clock: _Clock, seed: int):
    """Every rule at the paper's largest cluster; returns megha's run for
    the Pallas comparison."""
    from repro.simx import engine
    from repro.workload.synth import synthetic_trace

    wl = synthetic_trace(seed=seed, **FULL)
    sums, megha = {}, None
    for rule in engine.SCHEDULERS:
        with clock.phase(f"main_path.{rule}", workers=FULL["num_workers"],
                         dt=DT) as info:
            run = engine.simulate_workload(
                rule, wl, FULL["num_workers"], dt=DT, seed=seed
            )
            m = run_summary(run)
            info.update(m)
        check_complete(rule, m)
        sums[rule] = m
        if rule == "megha":
            megha = run
    o = sums["oracle"]
    for rule, m in sums.items():
        for q in ("p50", "p95"):
            check(o[q] <= m[q] + ORACLE_SLACK,
                  f"oracle {q} {o[q]} above {rule}'s {m[q]}")
    gaps = {r: round(m["p95"] - o["p95"], 6) for r, m in sums.items()}
    print(f"phase=main_path.oracle_bound ok=True p95_gap_over_oracle={gaps}",
          flush=True)
    return megha


def chip_vs_cpu(clock: _Clock, seed: int) -> None:
    """The same program on the chip and on the host CPU."""
    import jax
    import numpy as np

    from repro.simx import engine
    from repro.workload.synth import synthetic_trace

    cpu = jax.devices("cpu")[0]
    wl = synthetic_trace(seed=seed, **SMALL)
    counters = ("done", "lost", "rounds", "messages", "probes",
                "inconsistencies")
    for rule in ("megha", "sparrow"):
        with clock.phase(f"chip_vs_cpu.{rule}",
                         workers=SMALL["num_workers"], dt=DT) as info:
            runs = {}
            for where, dev in (("tpu", None), ("cpu", cpu)):
                with jax.default_device(dev):
                    run = engine.simulate_workload(
                        rule, wl, SMALL["num_workers"], dt=DT, seed=seed
                    )
                    runs[where] = (run_summary(run),
                                   np.asarray(run.state.task_finish))
            (a, fa), (b, fb) = runs["tpu"], runs["cpu"]
            diff = fa != fb
            info.update(
                {f"tpu_{k}": a[k] for k in counters + ("p50", "p95")},
                tasks_finish_differ=int(diff.sum()),
                max_finish_diff_s=float(np.max(np.abs(fa - fb), initial=0.0)),
                dp50=abs(a["p50"] - b["p50"]), dp95=abs(a["p95"] - b["p95"]),
            )
            if diff.any():
                # a float32 round-clock flip moves a finish by whole rounds
                steps = np.abs(fa[diff] - fb[diff]) / DT
                info["finish_diffs_whole_rounds"] = bool(
                    np.allclose(steps, np.round(steps), atol=1e-3)
                )
        check_complete(f"{rule}@tpu", a)
        check_complete(f"{rule}@cpu", b)
        for k in counters:
            check(a[k] == b[k], f"{rule}: {k} tpu={a[k]} cpu={b[k]}")
        for q in ("p50", "p95"):
            check(abs(a[q] - b[q]) <= CPU_DELAY_ATOL,
                  f"{rule}: {q} tpu={a[q]} cpu={b[q]}")


def pallas(clock: _Clock, seed: int, megha_jnp) -> None:
    """The compiled match kernel against the jnp reference, then megha
    end to end through it."""
    import numpy as np
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.match import match_ranks_batched
    from repro.simx import engine
    from repro.workload.synth import synthetic_trace

    rng = np.random.default_rng(seed)
    for g, w, block_rows in KERNEL_WIDTHS:
        with clock.phase("pallas.kernel", shape=f"[{g},{w}]",
                         block_rows=block_rows) as info:
            avail = jnp.asarray((rng.random((g, w)) < 0.4).astype(np.int8))
            n = jnp.asarray(rng.integers(0, w + 1, g), jnp.int32)
            got = np.asarray(match_ranks_batched(
                avail, n, block_rows=block_rows, interpret=False
            ))
            want = np.asarray(ref.match_ranks_batched_ref(avail, n))
            info["placed"] = int((got >= 0).sum())
            info["equal"] = bool(np.array_equal(got, want))
        check(info["equal"], f"kernel [{g},{w}] differs from the reference")
    wl = synthetic_trace(seed=seed, **FULL)
    with clock.phase("pallas.megha", workers=FULL["num_workers"],
                     dt=DT) as info:
        run = engine.simulate_workload(
            "megha", wl, FULL["num_workers"], dt=DT, seed=seed,
            use_pallas=True,
        )
        m = run_summary(run)
        same = bool(np.array_equal(np.asarray(run.state.task_finish),
                                   np.asarray(megha_jnp.state.task_finish)))
        info.update(m, task_finish_equal_jnp=same)
    want = run_summary(megha_jnp)
    check(m == want, f"megha use_pallas=True {m} != jnp path {want}")
    check(same, "megha use_pallas=True task_finish differs from the jnp path")


def drivers(clock: _Clock, seed: int) -> None:
    """The grid driver and the streaming driver."""
    import numpy as np

    from repro.simx import sweep
    from repro.simx.stream import run_steady_state
    from repro.workload.synth import PoissonArrivals, fixed_job_factory

    with clock.phase("drivers.fig2_sweep", rule="megha", **FIG2) as info:
        g = sweep.fig2_sweep("megha", trace_seed=seed, **FIG2)
        info.update(
            num_rounds=int(g["num_rounds"]),
            tasks_done=np.asarray(g["tasks_done"]).tolist(),
            p50=np.asarray(g["p50"]).round(4).tolist(),
            p95=np.asarray(g["p95"]).round(4).tolist(),
        )
    n_tasks = FIG2["num_jobs"] * FIG2["tasks_per_job"]
    check(bool(np.all(g["tasks_done"] == n_tasks)), "fig2: unfinished tasks")
    check(bool(np.all(g["jobs_done"] == FIG2["num_jobs"])), "fig2: jobs")
    check(bool(np.all(g["lost"] == 0)), "fig2: lost tasks")
    check(bool(np.all(np.isfinite(g["p95"]) & (g["p50"] <= g["p95"]))),
          "fig2: p50/p95")
    check(bool(np.all((g["mean_util"] > 0) & (g["mean_util"] <= 1))),
          "fig2: utilization")

    num_jobs = 400
    arrivals = PoissonArrivals(
        rate=0.8 * STEADY_W / STEADY_JOB_TASKS,
        job_factory=fixed_job_factory(STEADY_JOB_TASKS, 1.0),
        seed=seed, num_jobs=num_jobs,
    )
    with clock.phase("drivers.steady_state", rule="sparrow",
                     workers=STEADY_W, jobs=num_jobs) as info:
        run = run_steady_state(
            "sparrow", arrivals, STEADY_W, window_jobs=128,
            window_tasks=128 * STEADY_JOB_TASKS, rounds_per_refill=16,
        )
        info.update(refills=len(run.refills), rounds=run.rounds,
                    tasks_completed=run.tasks_completed,
                    tasks_admitted=run.tasks_admitted,
                    p99=run.quantile(0.99))
    check(len(run.refills) >= 4, f"steady state: {len(run.refills)} refills")
    for s in run.refills:
        check(s["admitted"] == s["completed"] + s["running"] + s["pending"]
              + s["unarrived"] + s["lost"], f"steady ledger: {s}")
    check(run.tasks_completed == run.tasks_admitted
          == num_jobs * STEADY_JOB_TASKS, "steady state: undrained")
    check(run.jobs_completed == num_jobs and run.lost == 0,
          "steady state: jobs or lost")


def four_chips(clock: _Clock, seed: int) -> None:
    """The mesh-sharded grids against the serial grids on one chip."""
    import numpy as np

    from repro.simx import shard, sweep

    mesh = shard.sweep_mesh(4)
    for rule in ("megha", "sparrow"):
        with clock.phase(f"four_chips.fig2.{rule}", **FOUR_FIG2) as info:
            serial = sweep.fig2_sweep(rule, trace_seed=seed, **FOUR_FIG2)
            sharded = shard.sharded_fig2_sweep(
                rule, mesh=mesh, trace_seed=seed, **FOUR_FIG2
            )
            info.update(
                n_devices=int(sharded["n_devices"]),
                p95=np.asarray(sharded["p95"]).round(4).tolist(),
            )
        check(int(sharded["n_devices"]) == 4, "mesh is not four chips")
        for k in ("p50", "p95"):
            np.testing.assert_allclose(sharded[k], serial[k], rtol=1e-5,
                                       err_msg=f"{rule}:{k}")
        for k in ("tasks_done", "jobs_done", "lost", "messages", "probes"):
            np.testing.assert_array_equal(sharded[k], serial[k],
                                          err_msg=f"{rule}:{k}")
    with clock.phase("four_chips.fig4_seed_sensitivity", rule="megha",
                     **FOUR_FIG4) as info:
        serial = sweep.fig4_sweep("megha", **FOUR_FIG4)
        sharded = shard.sharded_fig4_sweep("megha", mesh=mesh, **FOUR_FIG4)
        spread = np.ptp(np.asarray(serial["p95"]), axis=1)
        info.update(p95_seed_spread=spread.round(4).tolist())
    for k in ("p50", "p95"):
        np.testing.assert_allclose(sharded[k], serial[k], rtol=1e-5,
                                   err_msg=f"fig4:{k}")
    check(bool(np.any(spread > 0)), "fig4 grid is seed-insensitive")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded path")
    args = ap.parse_args(argv)

    dev = device()
    import jax

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = _Clock()
    n = len(jax.devices())
    print(f"phase=device platform={dev.platform} kind={dev.device_kind} "
          f"count={n} cache={cache}", flush=True)
    if args.four_chips:
        check(n >= 4, f"--four-chips needs 4 chips, JAX found {n}")
        four_chips(clock, args.seed)
    else:
        megha = main_path(clock, args.seed)
        chip_vs_cpu(clock, args.seed)
        pallas(clock, args.seed, megha)
        drivers(clock, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
