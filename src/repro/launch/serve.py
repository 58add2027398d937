"""Serving driver: Megha-scheduled request placement on decode slots.

  python -m repro.launch.serve --requests 200 --pods 2 --slots 16 \
      --frontends 2

Slots are continuous-batching lanes; the Megha engine (frontends = GMs with
eventually-consistent fleet views, pod controllers = LMs with ground truth)
places each request on a lane and holds it for its generation length in
ticks: request -> Megha placement -> simulated decode -> completion -> slot
reuse.  No model runs; the hold times stand in for decode work.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.serve.engine import MeghaServeEngine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--frontends", type=int, default=2)
    ap.add_argument("--mean-gen", type=int, default=12)
    ap.add_argument("--arrival", type=float, default=8.0, help="requests/tick")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rng = np.random.default_rng(args.seed)
    eng = MeghaServeEngine(
        num_frontends=args.frontends, num_pods=args.pods,
        slots_per_pod=args.slots, max_batch=args.slots * args.pods,
    )

    t0 = time.time()
    rid = 0
    while rid < args.requests:
        n = min(int(rng.poisson(args.arrival)), args.requests - rid)
        eng.submit([
            Request(rid + i, gen_len=1 + int(rng.poisson(args.mean_gen)))
            for i in range(n)
        ])
        rid += n
        eng.tick()
    stats = eng.run_until_drained()
    dt = time.time() - t0
    s = stats.summary()
    print(f"requests={s['completed']}/{args.requests} ticks={s['ticks']} "
          f"wall={dt:.1f}s ({s['completed']/dt:.0f} req/s)")
    print(f"placement: inconsistency_ratio={s['inconsistency_ratio']:.4f} "
          f"repartitions={s['repartitions']} "
          f"queue delay mean={s['mean_queue_delay']:.2f} p95={s['p95_queue_delay']:.2f} ticks")


if __name__ == "__main__":
    main()
