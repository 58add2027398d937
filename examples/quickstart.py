"""Quickstart: the paper in five minutes on a laptop.

1. Simulate Megha vs Sparrow/Eagle/Pigeon on a trace-like workload (Fig. 3).
2. The compiled simx sweep with the overhead columns: delay next to
   utilization, control messages, and inconsistency rate — the
   oracle-gap / eventual-consistency story in one table.
3. Show eventual consistency at work: inconsistency repair under load.
4. Run the Pallas match kernel (the GM's vectorized match operation).

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.core import fastpath as FP
from repro.sim.simulator import run_simulation
from repro.workload.synth import yahoo_like_trace

print("=" * 70)
print("1) 4-way scheduler comparison (scaled Yahoo-like trace, 1504 workers)")
print("=" * 70)
wl = yahoo_like_trace(num_jobs=600, total_tasks=9000, load=0.85,
                      num_workers=1504, seed=1)
results = {}
for sched in ("megha", "sparrow", "eagle", "pigeon"):
    m = run_simulation(sched, wl, num_workers=1504)
    s = m.summary()
    results[sched] = s
    print(f"  {sched:8s} median={s['all_median_delay']:.4f}s "
          f"p95={s['all_p95_delay']:.4f}s mean={s['all_mean_delay']:.4f}s "
          f"(inconsistencies/task={s['inconsistency_ratio']:.3f})")
for other in ("sparrow", "eagle", "pigeon"):
    f = results[other]["all_mean_delay"] / results["megha"]["all_mean_delay"]
    print(f"  -> Megha reduces mean delay vs {other} by {f:.1f}x")

print()
print("=" * 70)
print("2) simx sweep: delay AND the overhead it buys (256 workers, load 0.8)")
print("=" * 70)
from repro.simx import fig2_sweep

SPEC = dict(loads=(0.8,), num_seeds=1, num_workers=256, num_jobs=16,
            tasks_per_job=64, dt=0.05)
megha_kw = dict(num_gms=4, num_lms=4, heartbeat_interval=1.0)
print(f"  {'scheduler':8s} {'p50':>7s} {'p95':>7s} {'util':>6s} "
      f"{'msgs':>7s} {'inc/task':>8s}")
for sched in ("megha", "sparrow", "oracle"):
    r = fig2_sweep(sched, **SPEC, **(megha_kw if sched == "megha" else {}))
    print(f"  {sched:8s} {float(r['p50'][0, 0]):7.3f} "
          f"{float(r['p95'][0, 0]):7.3f} {float(r['mean_util'][0, 0]):6.3f} "
          f"{int(r['messages'][0, 0]):7d} "
          f"{float(r['inconsistency_rate'][0, 0]):8.4f}")
print("  -> megha trades inconsistency-repair traffic for oracle-like "
      "delay; sparrow pays in probe messages instead")

print()
print("=" * 70)
print("3) Eventually-consistent state: two GMs collide on a stale view")
print("=" * 70)
W = 4096
orders = FP.make_orders(W, num_gms=4, num_lms=4, seed=0)
truth = jnp.ones((W,), bool)
fresh = jnp.ones((W,), bool)
r1 = FP.gm_round(truth, fresh, orders[0], 3000, max_tasks=4096)
print(f"  GM_A placed {int((r1.workers >= 0).sum())} tasks, "
      f"{int(r1.n_inconsistent)} inconsistencies (fresh view)")
r2 = FP.gm_round(r1.truth, fresh, orders[1], 3000, max_tasks=4096)
print(f"  GM_B placed {int((r2.workers >= 0).sum())} tasks with a STALE view: "
      f"{int(r2.n_inconsistent)} inconsistencies -> repaired by LM piggyback")
print(f"  GM_B view now equals ground truth: {bool(jnp.array_equal(r2.view, r2.truth))}")

print()
print("=" * 70)
print("4) Pallas match kernel (interpreted on the CPU) vs jnp oracle")
print("=" * 70)
from repro.kernels import ops, ref

rng = np.random.default_rng(0)
avail = jnp.asarray((rng.random(50_000) < 0.3).astype(np.int8))
a1, p1 = ops.match_tasks(avail, 1000, 1024, use_pallas=True)
a2, p2 = ref.match_tasks_ref(avail, 1000, 1024)
print(f"  50k-worker bitmap, 1000 tasks: kernel == oracle: "
      f"{bool(jnp.array_equal(a1, a2))}, placed={int(p1)}")
print("done.")
