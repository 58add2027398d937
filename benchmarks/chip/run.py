#!/usr/bin/env python3
"""Chip benchmark of the compiled scheduler simulator.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the root of the checkout:

* the cell's configuration file (``configs/<config>.json``): the cluster,
  the trace generator's parameters and the guarantees it keeps;
* its traffic file (``traffic/<traffic>.json``): the rule, the driver and
  the driver's unit of work;
* the driver, ``drivers/<driver>.py``: a ``Driver(config, traffic, seed,
  devices)`` that builds the program, and has ``warm()`` (compile, bring
  the state to where the window starts, and time one unit of work,
  ``unit_s``), ``send()`` (dispatch one unit, waiting only while more
  than ``ahead`` are in flight), ``drain()`` (wait for every unit sent),
  ``retired`` (the rounds each of its ``datacenters`` advanced in the
  units waited for) and ``verify()`` (the reference's numbers, tasks
  attempted and failed; what the compared rounds held in ``work``), and
  names its host spans in ``UNIT_SPANS``;
* one reader per metric, ``metrics/<metric>.py``: ``read(window)``
  returns the metric, or ``None`` where it finds nothing to read.

A run refuses anything but a TPU with at least the cell's chips, builds
the trace from ``--seed``, compiles and brings the state to the window's
start (the set-up), then sends units of work, about ``AHEAD_S`` seconds
of them ahead of the one it waits for, until ``--seconds`` have passed;
it then sends nothing more and waits for all it sent, and the window
closes after that wait.  Once the window has closed and the device's peak memory is read,
the reference (``check.py``) checks what the window produced.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace
1``), then ``checks``, each number the reference compared with its limit;
the last lines of standard error repeat those numbers.  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of a window of ``TRACE_SECONDS``.

The persistent compilation cache is ``<checkout>/.jax_compile_cache``,
whatever the environment says, so that only a cell's first run in a
checkout compiles the programs that every seed shares.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: The persistent compilation cache: one fixed directory in the checkout.
CACHE_DIR = ROOT / ".jax_compile_cache"
#: A traced run's window, before its last wait: a few seconds with it, so
#: that the profiler's device buffer holds every operation (the busiest
#: cell records about two million operations a second).
TRACE_SECONDS = 1.5
#: Device seconds of work kept in flight ahead of the unit waited for, so
#: that the chip runs on while the host stands still; a traced run keeps
#: less, so that its last wait stays short.  At most ``MAX_AHEAD`` units.
AHEAD_S = 5.0
TRACE_AHEAD_S = 0.5
MAX_AHEAD = 16


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, bench: dict) -> tuple[dict, dict, dict]:
    """``(cell, configuration, traffic)`` of the cell called ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / conf["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


def cell_metrics(cell: str, bench: dict, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) if m["moves"] in names]


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Clock:
    """XLA compile seconds and persistent-cache hits and misses, from
    JAX's monitoring events, counted from the process's first ``get()``."""

    _one = None

    @classmethod
    def get(cls) -> "Clock":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def chips(n: int) -> list:
    """The first ``n`` TPU devices; anything else ends the run with no
    result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {devs[0].platform!r}); "
                         "this benchmark measures nothing elsewhere")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_cache() -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()


@dataclass
class Window:
    """What one measured window did, for the metric readers."""

    setup_s: float
    window_s: float
    rounds: int
    dt: float
    datacenters: int
    reduced: object = None     # xtrace.Reduced of a traced window


@contextlib.contextmanager
def profiled(on: bool):
    """Trace the block with the JAX profiler into a scratch directory;
    yields a dict that receives ``xtrace.read``'s result."""
    got: dict = {}
    if not on:
        yield got
        return
    import jax

    import xtrace

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(log_dir)
        try:
            yield got
        finally:
            jax.profiler.stop_trace()
        got["trace"] = xtrace.read(xtrace.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def run_cell(config: dict, traffic: dict, metrics: list[dict], *,
             seed: int, seconds: float, trace: bool, devices,
             program_cluster: dict | None = None, t0: float = T0) -> dict:
    """One run of one cell; returns the result line's object.
    ``program_cluster`` builds the program on another cluster than the
    configuration states (the control), while the reference still holds
    it to the stated one."""
    import jax

    import xtrace

    clock = Clock.get()
    c_start = (clock.compiles, clock.compile_s, clock.hits, clock.misses)
    drivers = load_module("drivers", traffic["driver"])
    driver = drivers.Driver(config, traffic, seed, devices,
                            program_cluster=program_cluster)
    driver.warm()
    c0 = (clock.compiles, clock.compile_s)
    setup_compile = dict(compile_s=clock.compile_s - c_start[1],
                         compiles=clock.compiles - c_start[0],
                         cache_hits=clock.hits - c_start[2],
                         cache_misses=clock.misses - c_start[3])
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    ahead_s = TRACE_AHEAD_S if trace else AHEAD_S
    driver.ahead = min(MAX_AHEAD, max(1, math.ceil(ahead_s / driver.unit_s)))
    retired = driver.retired
    with profiled(trace) as prof:
        with jax.profiler.TraceAnnotation("window"):
            start = time.perf_counter()
            while time.perf_counter() - start < seconds:
                driver.send()
            driver.drain()
            now = time.perf_counter()
    rounds = driver.retired - retired
    window_s = now - start
    setup_s = start - t0
    info = dict(setup_s=setup_s, **setup_compile,
                window_compiles=clock.compiles - c0[0],
                window_compile_s=clock.compile_s - c0[1],
                window_s=window_s, rounds=rounds, ahead=driver.ahead,
                unit_s=driver.unit_s, datacenters=driver.datacenters)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    v0 = time.perf_counter()
    nums, attempted, failed = driver.verify()
    info["verify_s"] = time.perf_counter() - v0
    info["ref_work"] = json.dumps(driver.work, separators=(",", ":"))
    print("run " + " ".join(f"{k}={v}" for k, v in info.items()), flush=True)
    from check import LIMITS

    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in nums.items()}
    win = Window(setup_s=setup_s, window_s=window_s, rounds=rounds,
                 dt=config["cluster"]["dt"], datacenters=driver.datacenters)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": attempted, "failed": failed}
    if trace:
        red = xtrace.reduce(*prof["trace"], window="window",
                            labels=drivers.UNIT_SPANS)
        win.reduced = red
        device["busy_s"] = red.busy_mean_s
        device["window_s"] = red.window_s
    values = {}
    for m in metrics:
        v = load_module("metrics", m["name"]).read(win)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = values
    out["device"] = device
    if trace:
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.op_s[:10]],
            "idle_gaps": [[n, s] for n, s in red.gaps[:10]],
        }
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic = find_cell(args.workload, bench)
    metrics = cell_metrics(cell["name"], bench, bool(args.trace))
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    devices = chips(cell["chips"])
    enable_cache()
    out = run_cell(config, traffic, metrics, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devices)
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(f"correct={out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
