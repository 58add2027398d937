"""Named scopes of the round pipeline, and the set-up spans and compile
counter of ``repro.simx.spans``.

* every rule's lowered step carries the runtime's stage scopes and each of
  its own dispatch sections (``simx.<rule>.<section>``), with telemetry
  and provenance under ``simx.telemetry`` / ``simx.provenance``;
* the scopes are metadata only: the optimized program is the same with
  ``jax.named_scope`` made a no-op;
* the queue passes that run at the queues' live width keep their rule's
  section scopes inside the ``conditional`` branches that hold them;
* ``simx.build`` times the rule builders (the compiles inside it counted
  apart), the compile counter names the chunk runner ``simx_chunk`` and
  can stop at its last call, and reading the runner's optimized HLO
  afterwards compiles and counts nothing.
"""

import contextlib
import dataclasses
import random
import re
from collections import defaultdict

import jax
import pytest

from repro.core.base import Job
from repro.simx import engine, spans
from repro.simx import runtime as rt
from repro.simx.faults import empty_schedule
from repro.simx.provenance import init_provenance
from repro.simx.state import SimxConfig, export_workload
from repro.workload.traces import Workload

RUNTIME = ("simx.faults", "simx.complete", "simx.metrics", "simx.done")
SECTIONS = {
    "megha": ("rollback", "views", "internal", "borrow", "head"),
    "sparrow": ("compact", "insert", "bind"),
    "pigeon": ("rollback", "wfq", "match", "launch"),
    "eagle": ("rollback", "compact", "insert", "drain", "bind", "central"),
    "oracle": ("rollback", "window", "match", "launch"),
}
#: rules whose dispatch computes telemetry / provenance extras of its own
#: (megha's are computed inside its internal and borrow sections)
EXTRAS = ("megha", "sparrow", "pigeon", "eagle", "oracle")


@pytest.fixture(scope="module")
def mixed():
    """Long and short jobs on 64 workers: eagle's central long path and
    pigeon's low queue both compile in."""
    rng = random.Random(3)
    jobs, t = [], 0.0
    for i in range(12):
        durs = [20.0] * 4 if i % 4 == 0 else [1.0] * 8
        jobs.append(Job(job_id=i, submit_time=t, durations=durs))
        t += rng.expovariate(1.0 / 0.4)
    tasks = export_workload(Workload(name="mixed", jobs=jobs))
    cfg = SimxConfig(num_workers=64, num_gms=2, num_lms=2, dt=0.02,
                     heartbeat_interval=1.0, group_size=16)
    return cfg, tasks


def scopes(text: str) -> set:
    return set(re.findall(r"simx\.[a-z]+(?:\.[a-z]+)?", text))


def lowered(mixed, name, **kw) -> str:
    cfg, tasks = mixed
    rule = rt.get_rule(name)
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0), **kw)
    state = rule.init(cfg, tasks)
    if kw.get("provenance"):
        state = (state, init_provenance(tasks.num_tasks))
    if kw.get("telemetry"):
        return jax.jit(step).lower(state).as_text(debug_info=True)
    runner = engine.make_chunk_runner(step, 2)
    return runner.lower(state).as_text(debug_info=True)


@pytest.mark.parametrize("name", sorted(SECTIONS))
def test_every_stage_and_section_is_scoped(mixed, name):
    cfg, _ = mixed
    got = scopes(lowered(mixed, name, faults=empty_schedule(cfg.num_workers,
                                                          cfg.num_gms)))
    want = set(RUNTIME) | {f"simx.{name}.{s}" for s in SECTIONS[name]}
    assert want <= got, want - got
    assert not {s for s in got if s.startswith("simx.")} - want - {
        "simx.telemetry", "simx.provenance"}


@pytest.mark.parametrize("name", EXTRAS)
@pytest.mark.parametrize("extra", ["telemetry", "provenance"])
def test_telemetry_and_provenance_have_their_own_scopes(mixed, name, extra):
    assert f"simx.{extra}" in scopes(lowered(mixed, name, **{extra: True}))
    assert f"simx.{extra}" not in scopes(lowered(mixed, name))


def _optimized(mixed, name) -> str:
    cfg, tasks = mixed
    rule = rt.get_rule(name)
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0))
    text = engine.make_chunk_runner(step, 2).lower(
        rule.init(cfg, tasks)).compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)*", "\n", text)
    names: dict = {}
    # instruction names may follow the scopes (XLA names a merged op from
    # its locations); the program is what must not change
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%{len(names)}"),
                  text)


@pytest.mark.parametrize("name", sorted(SECTIONS))
def test_scopes_leave_the_optimized_program_alone(mixed, name, monkeypatch):
    scoped = _optimized(mixed, name)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    assert _optimized(mixed, name) == scoped


_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INST = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost_scope(op_name: str):
    return next((seg for seg in reversed(op_name.split("/"))
                 if seg.startswith("simx.")), None)


@pytest.mark.parametrize("name", ["sparrow", "eagle"])
def test_width_branches_keep_their_section_scopes(mixed, name):
    """With a cap wide enough for a narrow width (R = 64), the optimized
    runner holds one ``conditional`` for each of compaction, insertion's
    held test and late binding's queue passes.  Each conditional carries
    its section's ``simx.<rule>.<section>`` scope, and so does every op in
    its branches that has a ``simx.`` scope of its own, so the
    benchmark's stage split still places them (an op with none, such as a
    branch's parameters, takes its container's)."""
    cfg, tasks = mixed
    cfg = dataclasses.replace(cfg, reserve_cap=64)
    rule = rt.get_rule(name)
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0))
    text = engine.make_chunk_runner(step, 2).lower(
        rule.init(cfg, tasks)).compile().as_text()
    body, conds, comp = defaultdict(list), [], None
    for line in text.splitlines():
        if m := _COMP.match(line):
            comp = m.group(1)
        elif (m := _INST.match(line)) and comp is not None:
            body[comp].append(m.group(2))
            if " conditional(" in m.group(2):
                conds.append(m.group(2))
    sections = set()
    for inst in conds:
        scope = innermost_scope(_OP_NAME.search(inst).group(1))
        assert scope in {f"simx.{name}.{s}" for s in ("compact", "insert", "bind")}
        sections.add(scope)
        scoped = 0
        for branch in _BRANCHES.search(inst).group(1).split(","):
            for op in body[branch.strip().lstrip("%")]:
                if (m := _OP_NAME.search(op)) and innermost_scope(m.group(1)):
                    assert innermost_scope(m.group(1)) == scope, op
                    scoped += 1
        assert scoped > 0
    assert len(conds) == len(sections) == 3


def test_build_span_and_compile_counter(mixed):
    cfg, tasks = mixed
    rule = rt.get_rule("sparrow")
    b0, c0 = spans.totals["simx.build"], spans.compile_in["simx.build"]
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(1))
    built = spans.totals["simx.build"] - b0
    # what the builder's eager ops compile inside the span is counted apart
    assert built > 0
    assert 0 <= spans.compile_in["simx.build"] - c0 < built
    runner = engine.make_chunk_runner(step, 3)
    assert spans.programs["simx_chunk"] is runner
    assert runner.hlo_text() is None          # not run yet
    key = ("backend", "jit(simx_chunk)")
    s0 = spans.compile_s[key]
    state = rule.init(cfg, tasks)
    state, _ = runner(state)
    s1 = spans.compile_s[key]
    assert s1 > s0
    assert spans.compile_s[("trace", "simx_chunk")] > 0
    assert spans.compile_s[("lower", "jit(simx_chunk)")] > 0
    runner(state)                             # the same program again
    assert spans.compile_s[key] == s1
    # compiles after the runner's last call are left out of ``until``
    last = runner.last_call
    until = spans.phase_s("backend", until=last)
    assert until == pytest.approx(spans.phase_s("backend"))
    jax.jit(lambda x: x * 7 + 1)(state.t)
    assert spans.phase_s("backend", until=last) == until
    assert spans.phase_s("backend") > until
    before = (dict(spans.compile_s), dict(spans.cache))
    text = runner.hlo_text()
    assert "simx.sparrow.insert" in text and "simx.done" in text
    assert (dict(spans.compile_s), dict(spans.cache)) == before
    assert spans.phase_s("backend") >= spans.compile_s[key]


def test_a_span_nested_in_itself_counts_once():
    t0, c0 = spans.totals["test.outer"], spans.compile_in["test.outer"]
    with spans.span("test.outer"):
        with spans.span("test.outer"):
            jax.jit(lambda x: x * 5 - 2)(jax.numpy.arange(7))
        with spans.span("test.inner"):
            pass
    outer = spans.totals["test.outer"] - t0
    assert outer > 0
    assert spans.totals["test.inner"] <= outer
    # the compile inside counted once, and within the span's time
    assert 0 < spans.compile_in["test.outer"] - c0 < outer
