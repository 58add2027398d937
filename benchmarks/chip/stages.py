"""Device time of a traced window by stage of the round pipeline.

The program names each stage with ``jax.named_scope`` (``simx.faults``,
``simx.complete``, ``simx.metrics``, ``simx.done``, and each rule's
dispatch sections ``simx.<rule>.<section>``).  XLA keeps the scopes in
each HLO instruction's ``op_name``; the trace reduction (``xtrace``) sums
op seconds by HLO name.  ``stage_s`` joins the two: it reads the optimized
HLO of the program's chunk runner after the window has closed
(``repro.simx.spans.programs["simx_chunk"].hlo_text()``, the compiled
program found again, nothing compiled), and attributes each op, in order:

1. own name stack: the innermost ``simx.`` segment of the op's
   ``op_name``, or, where it has none, the stage most of the instructions
   fused into it carry;
2. container: the stage of the ``while`` / ``conditional`` / ``call`` or
   fusion whose computation holds the op;
3. source line: the stage that every scoped instruction at one of the
   op's source lines carries, walking its stack from the innermost frame
   out (the ops of a nested, inlined ``jit``, such as a ``cumsum`` lowered
   to ``reduce-window``, keep only their own relative names, but their
   frames still reach the user's call site);
4. otherwise unattributed, the stage ``""``.

Every op lands in exactly one stage, so a window's stage seconds sum to
its op seconds.  A program without scopes, or without ``spans``, has no
stage time: ``stage_s`` is then ``None``.
"""

from __future__ import annotations

import re
import sys
from collections import Counter

#: The runtime's own stages (``runtime.compose_step`` and the chunk
#: runner's done flag).
RUNTIME = ("simx.faults", "simx.complete", "simx.metrics", "simx.done")
#: The chunk runner's name in ``repro.simx.spans.programs``.
RUNNER = "simx_chunk"

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INST = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_SOURCE = re.compile(r'source_file="([^"]*)" source_line=(\d+)')
_CALLS = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPCODE = re.compile(r"\b([a-z][a-z0-9\-]*)\(")
_TABLE = re.compile(r"^(\d+) (.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")


def scope_of(op_name: str) -> str | None:
    """The innermost ``simx.`` segment of a name stack."""
    for seg in reversed(op_name.split("/")):
        if seg.startswith("simx."):
            return seg
    return None


class Hlo:
    """The instructions of an HLO module's text: per instruction, its
    computation, name stack, source lines (innermost first) and the
    computations it calls."""

    def __init__(self, text: str):
        self.comp: dict[str, str] = {}
        self.opcode: dict[str, str] = {}
        self.op_name: dict[str, str] = {}
        self.lines: dict[str, list[tuple[str, int]]] = {}
        self.calls: dict[str, list[str]] = {}
        self.body: dict[str, list[str]] = {}     # computation -> instructions
        self.callers: dict[str, list[str]] = {}  # computation -> instructions
        tables: dict[str, dict[int, str]] = {}
        table, comp = None, None
        pending = []
        for line in text.splitlines():
            m = _TABLE.match(line)
            if table and m:
                tables[table][int(m.group(1))] = m.group(2)
                continue
            if line in ("FileNames", "FunctionNames", "FileLocations",
                        "StackFrames"):
                table = line
                tables[table] = {}
                continue
            table = None
            m = _COMP.match(line)
            if m:
                comp = m.group(1)
                self.body[comp] = []
                continue
            m = _INST.match(line)
            if m and comp is not None:
                name, rest = m.groups()
                self.comp[name] = comp
                self.body[comp].append(name)
                op = _OPCODE.search(rest)
                self.opcode[name] = op.group(1) if op else ""
                on = _OP_NAME.search(rest)
                self.op_name[name] = on.group(1) if on else ""
                called = _CALLS.findall(rest)
                for b in _BRANCHES.findall(rest):
                    called += [c.strip().lstrip("%") for c in b.split(",")]
                self.calls[name] = called
                for c in called:
                    self.callers.setdefault(c, []).append(name)
                pending.append((name, rest))
        chains = _Frames(tables)
        for name, rest in pending:
            locs = []
            src = _SOURCE.search(rest)
            if src:
                locs.append((src.group(1), int(src.group(2))))
            fr = _FRAME.search(rest)
            if fr:
                locs += [loc for loc in chains.chain(int(fr.group(1)))
                         if loc not in locs]
            self.lines[name] = locs

    def inside(self, name: str) -> list[str]:
        """Every instruction of the computations ``name`` calls, at any
        depth."""
        out, todo, seen = [], list(self.calls.get(name, ())), set()
        while todo:
            c = todo.pop()
            if c in seen:
                continue
            seen.add(c)
            for n in self.body.get(c, ()):
                out.append(n)
                todo.extend(self.calls.get(n, ()))
        return out


class _Frames:
    """The module's stack-frame tables: frame id -> (file, line) chain."""

    def __init__(self, tables: dict[str, dict[int, str]]):
        files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
        self.loc = {}
        for k, v in tables.get("FileLocations", {}).items():
            f = dict((a, int(b)) for a, b in _FIELD.findall(v))
            self.loc[k] = (files.get(f.get("file_name_id"), ""), f.get("line", 0))
        self.parent = {}
        self.frame_loc = {}
        for k, v in tables.get("StackFrames", {}).items():
            f = dict((a, int(b)) for a, b in _FIELD.findall(v))
            self.frame_loc[k] = f.get("file_location_id")
            self.parent[k] = f.get("parent_frame_id")

    def chain(self, frame: int) -> list[tuple[str, int]]:
        out, seen = [], set()
        while frame in self.frame_loc and frame not in seen:
            seen.add(frame)
            loc = self.loc.get(self.frame_loc[frame])
            if loc:
                out.append(loc)
            frame = self.parent.get(frame)
        return out


def attribute(text: str) -> dict[str, str]:
    """HLO instruction name -> stage (``""`` where no rule finds one)."""
    hlo = Hlo(text)
    own = {n: scope_of(o) for n, o in hlo.op_name.items()}
    votes: dict[tuple[str, int], Counter] = {}
    for n, s in own.items():
        if s:
            for loc in hlo.lines[n]:
                votes.setdefault(loc, Counter())[s] += 1
    memo: dict[str, str | None] = {}

    def by_name(n: str) -> str | None:
        if own[n] or hlo.opcode[n] != "fusion":
            return own[n]
        inner = Counter(own[i] for i in hlo.inside(n) if own[i])
        return inner.most_common(1)[0][0] if inner else None

    def by_container(n: str) -> str | None:
        if n in memo:
            return memo[n]
        memo[n] = None
        got = by_name(n)
        if got is None:
            for caller in hlo.callers.get(hlo.comp[n], ()):
                got = by_container(caller)
                if got:
                    break
        memo[n] = got
        return got

    def by_line(n: str) -> str | None:
        found = Counter()
        for i in [n] + hlo.inside(n):
            for loc in hlo.lines[i]:
                v = votes.get(loc)
                if v and len(v) == 1:
                    found[next(iter(v))] += 1
                    break
        return found.most_common(1)[0][0] if found else None

    return {n: by_container(n) or by_line(n) or "" for n in hlo.comp}


def reduce_stages(op_s, stage_of: dict[str, str]) -> dict[str, float]:
    """Op seconds (``xtrace.Reduced.op_s``) summed by stage; an op the
    module does not hold is unattributed."""
    out: dict[str, float] = {}
    for name, s in op_s:
        st = stage_of.get(name.lstrip("%"), "")
        out[st] = out.get(st, 0.0) + s
    return out


def stage_s(w) -> dict[str, float] | None:
    """Op seconds by stage of a traced window ``w`` (``run.Window``), or
    ``None`` where there is no trace, no program runner or no scope."""
    red = getattr(w, "reduced", None)
    if red is None or not red.op_s:
        return None
    if not hasattr(w, "stage_s"):
        prog = getattr(program_spans(), "programs", {}).get(RUNNER)
        text = prog.hlo_text() if prog is not None else None
        stage_of = attribute(text) if text else {}
        w.stage_s = (reduce_stages(red.op_s, stage_of)
                     if any(stage_of.values()) else None)
    return w.stage_s


def stage_ms(w, *stages: str) -> float | None:
    """Milliseconds per round of one datacenter that ``stages`` took on
    the device: their op seconds over the rounds times the datacenters
    the window advanced (``device_ms_per_round``'s denominator)."""
    got = stage_s(w)
    if got is None or not w.rounds:
        return None
    return 1e3 * sum(got.get(s, 0.0) for s in stages) / (
        w.rounds * w.datacenters)


def program_spans():
    """The program's ``repro.simx.spans`` module, where it has one."""
    return sys.modules.get("repro.simx.spans")
