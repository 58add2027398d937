"""Reduce a JAX profiler trace to device busy time, op time and idle gaps.

``read(path)`` loads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain ``Event`` lists: per device plane that has an ``XLA Ops`` line
(``/device:TPU:<n>``), its events on every line (``XLA Modules``, ``XLA
Ops``, ``Async XLA Ops``); and the host spans (every event on the
``/host:CPU`` plane, the harness's ``jax.profiler.TraceAnnotation`` spans
among them).  ``reduce`` works on that plain form, so the tests can
hand-build a trace.

Definitions (all clipped to the traced window, the harness's ``window``
span):

* busy: the union of the intervals in which a program or an operation ran
  on a device;
* idle gap: an interval of the window in which nothing ran on the first
  device, named by the harness span that overlaps it most;
* op time: the summed device duration of each ``XLA Ops`` operation, by
  its HLO name (``%fusion.12``), over all devices; loops, conditionals and
  calls are left out, since the operations inside them are listed.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass


#: The lines of a device plane that hold programs and operations.
DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
#: HLO operations that contain other listed operations (a conditional is
#: named ``%cond.<n>`` on the TPU).
CONTAINERS = ("%while", "%cond", "%call")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    op: bool = False        # an ``XLA Ops`` event

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` trace wrote under
    ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {found}")
    return found[0]


def read(path: str) -> tuple[dict[str, list[Event]], list[Event]]:
    """``(device events by plane name, host events)`` of an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and any(
                ln.name == "XLA Ops" for ln in lines):
            devices[plane.name] = [
                Event(e.name.split(" = ")[0], e.start_ns, e.duration_ns,
                      op=ln.name == "XLA Ops")
                for ln in lines if ln.name in DEVICE_LINES
                for e in ln.events
            ]
        elif plane.name == "/host:CPU":
            host.extend(Event(e.name, e.start_ns, e.duration_ns)
                        for ln in lines for e in ln.events)
    return devices, host


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The complement of merged ``busy`` intervals within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(gap, spans: list[Event]) -> str:
    best, name = 0.0, "host:untraced"
    for sp in spans:
        ov = min(gap[1], sp.end_ns) - max(gap[0], sp.start_ns)
        if ov > best:
            best, name = ov, sp.name
    return name


@dataclass
class Reduced:
    window_s: float
    busy_s: dict[str, float]          # per device plane
    op_s: list[tuple[str, float]]     # op name -> seconds, largest first
    gaps: list[tuple[str, float]]     # labelled idle gaps, longest first

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)


def reduce(devices: dict[str, list[Event]], host: list[Event], *,
           window: str = "window", labels=()) -> Reduced:
    """Busy seconds per device, op seconds and labelled idle gaps within
    the host span named ``window``; gaps are named by the host spans whose
    names are in ``labels``."""
    win = [e for e in host if e.name == window]
    if len(win) != 1:
        raise ValueError(f"expected one {window!r} span, found {len(win)}")
    lo, hi = win[0].start_ns, win[0].end_ns
    busy, ops = {}, {}
    merged0 = None
    for plane in sorted(devices):
        evs = devices[plane]
        merged = union(((e.start_ns, e.end_ns) for e in evs), lo, hi)
        busy[plane] = sum(e - s for s, e in merged) / 1e9
        if merged0 is None:
            merged0 = merged
        for e in evs:
            if not e.op or e.name.startswith(CONTAINERS):
                continue
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                ops[e.name] = ops.get(e.name, 0.0) + d / 1e9
    spans = [e for e in host if e.name in set(labels)]
    idle = [(_label(g, spans), (g[1] - g[0]) / 1e9)
            for g in gaps(merged0 or [], lo, hi)]
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy,
        op_s=sorted(ops.items(), key=lambda kv: -kv[1]),
        gaps=sorted(idle, key=lambda kv: -kv[1]),
    )
