"""Stage attribution of a traced window's ops (``stages.py``) and the
per-stage and set-up readers, on a hand-built module and on the program's
own chunk runner."""

import tinycells  # noqa: F401  first: the CPU, and the program on the path

import jax
import pytest

import run
import stages
import xtrace
from repro.simx import engine, spans
from repro.simx import runtime as rt
from repro.simx.state import SimxConfig, export_workload
from repro.workload.synth import synthetic_trace

BENCH = run.load_json(run.ROOT / "BENCHMARK.json")
STAGE_READERS = [m["name"] for m in BENCH["per_layer"]
                 if m["name"].startswith("stage_ms_")]
SETUP_READERS = ["setup_build_s", "setup_compile_s"]

#: A module in the form XLA prints it: a scoped fusion, an unscoped fusion
#: of scoped ops, a reduce-window of a nested jit that kept only its own
#: name (its frame reaches a line that only ``compact`` ops use), a
#: conditional whose branch copies carry no name, and a loop-carry copy.
HLO = """\
HloModule jit_simx_chunk, entry_computation_layout={(s32[4]{0})->s32[4]{0}}

FileNames
1 "sparrow.py"
2 "helpers.py"

FunctionNames
1 "dispatch"
2 "helper"

FileLocations
1 {file_name_id=1 function_name_id=1 line=10 end_line=10 column=1 end_column=9}
2 {file_name_id=2 function_name_id=2 line=5 end_line=5 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}

%fused_mul (p.1: s32[4]) -> s32[4] {
  %p.1 = s32[4]{0} parameter(0)
  ROOT %mul.1 = s32[4]{0} multiply(%p.1, %p.1), metadata={op_name="jit(simx_chunk)/while/body/simx.sparrow.insert/mul" stack_frame_id=1}
}

%sum (a.1: s32[], b.1: s32[]) -> s32[] {
  %a.1 = s32[] parameter(0)
  %b.1 = s32[] parameter(1)
  ROOT %reduce_window_sum.5 = s32[] add(%a.1, %b.1), metadata={op_name="reduce_window_sum" stack_frame_id=2}
}

%fused_rw (q.1: s32[4]) -> s32[4] {
  %q.1 = s32[4]{0} parameter(0)
  %c.1 = s32[] constant(0)
  ROOT %reduce-window.1 = s32[4]{0} reduce-window(%q.1, %c.1), window={size=4 pad=3_0}, to_apply=%sum
}

%branch_0 (x.1: s32[4]) -> s32[4] {
  %x.1 = s32[4]{0} parameter(0)
  ROOT %copy.3 = s32[4]{0} copy(%x.1)
}

%branch_1 (x.2: s32[4]) -> s32[4] {
  ROOT %x.2 = s32[4]{0} parameter(0)
}

%body (g.1: s32[4]) -> s32[4] {
  %g.1 = s32[4]{0} parameter(0)
  %fusion.1 = s32[4]{0} fusion(%g.1), kind=kLoop, calls=%fused_mul, metadata={op_name="jit(simx_chunk)/while/body/simx.sparrow.insert/mul" stack_frame_id=1}
  %fusion.2 = s32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_mul
  %wrapped_reduce-window = s32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_rw
  %fusion.4 = s32[4]{0} fusion(%g.1), kind=kLoop, calls=%fused_mul, metadata={op_name="jit(simx_chunk)/while/body/simx.sparrow.compact/sub" stack_frame_id=2}
  %pred.1 = pred[] constant(true)
  %conditional.1 = s32[4]{0} conditional(%pred.1, %fusion.4, %fusion.4), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(simx_chunk)/while/body/simx.megha.borrow/cond"}
  ROOT %copy.9 = s32[4]{0} copy(%conditional.1)
}

%cond (g.2: s32[4]) -> pred[] {
  %g.2 = s32[4]{0} parameter(0)
  ROOT %t.1 = pred[] constant(true)
}

ENTRY %main.1 (x.9: s32[4]) -> s32[4] {
  %x.9 = s32[4]{0} parameter(0)
  ROOT %while.1 = s32[4]{0} while(%x.9), condition=%cond, body=%body, metadata={op_name="jit(simx_chunk)/while"}
}
"""

#: Op seconds as ``xtrace.reduce`` lists them (containers left out), and
#: one op of another module.
OP_S = [("%fusion.1", 1.0), ("%fusion.2", 2.0),
        ("%wrapped_reduce-window", 4.0), ("%copy.3", 8.0), ("%copy.9", 16.0),
        ("%fusion.4", 32.0), ("%fusion.77", 64.0)]


class _Runner:
    def __init__(self, text):
        self.text = text

    def hlo_text(self):
        return self.text


def window(op_s=OP_S, rounds=10, datacenters=2):
    w = run.Window(setup_s=1.0, window_s=1.0, rounds=rounds, dt=0.01,
                   datacenters=datacenters)
    w.reduced = xtrace.Reduced(window_s=1.0, busy_s={"/device:TPU:0": 1.0},
                               op_s=op_s, gaps=[])
    return w


def test_attribution_order_on_a_hand_built_module():
    st = stages.attribute(HLO)
    assert st["fusion.1"] == "simx.sparrow.insert"      # own name stack
    assert st["fusion.2"] == "simx.sparrow.insert"      # ops fused into it
    assert st["fusion.4"] == "simx.sparrow.compact"
    assert st["copy.3"] == "simx.megha.borrow"          # its container
    # a nested jit's op: no scope of its own or around it, but its source
    # line is one that only ``compact`` ops use
    assert st["wrapped_reduce-window"] == "simx.sparrow.compact"
    assert st["copy.9"] == ""                           # nothing to go by
    assert stages.scope_of("a/simx.x.y/b/simx.telemetry/c") == "simx.telemetry"
    assert stages.scope_of("jit(simx_chunk)/while") is None


def test_stage_seconds_sum_to_the_op_time(monkeypatch):
    monkeypatch.setitem(spans.programs, stages.RUNNER, _Runner(HLO))
    w = window()
    got = stages.stage_s(w)
    assert got == pytest.approx({"simx.sparrow.insert": 3.0,
                                 "simx.sparrow.compact": 36.0,
                                 "simx.megha.borrow": 8.0, "": 80.0})
    assert sum(got.values()) == pytest.approx(sum(s for _, s in OP_S))


def test_stage_readers_divide_by_rounds_times_datacenters(monkeypatch):
    monkeypatch.setitem(spans.programs, stages.RUNNER, _Runner(HLO))
    w = window(rounds=10, datacenters=2)
    read = {n: run.load_module("metrics", n).read(w) for n in STAGE_READERS}
    assert read["stage_ms_sparrow_insert"] == pytest.approx(1e3 * 3.0 / 20)
    assert read["stage_ms_sparrow_compact"] == pytest.approx(1e3 * 36.0 / 20)
    assert read["stage_ms_megha_borrow"] == pytest.approx(1e3 * 8.0 / 20)
    assert read["stage_ms_unscoped"] == pytest.approx(1e3 * 80.0 / 20)
    assert read["stage_ms_runtime"] == 0.0
    # every stage read once: the cells' readers together cover the op time
    per_cell = {}
    for m in BENCH["per_layer"]:
        if m["name"] in read:
            for cell in m["workloads"]:
                per_cell.setdefault(cell, set()).add(m["name"])
    assert set(per_cell) == {w["name"] for w in BENCH["workloads"]}
    for cell, names in per_cell.items():
        prefix = "stage_ms_" + cell.split(".")[1] + "_"
        assert {"stage_ms_runtime", "stage_ms_unscoped"} <= names
        assert all(n.startswith(prefix) for n in
                   names - {"stage_ms_runtime", "stage_ms_unscoped"})


@pytest.mark.parametrize("text", [None, HLO.replace("simx.", "other.")])
def test_a_program_without_runner_or_scopes_reads_nothing(monkeypatch, text):
    monkeypatch.setitem(spans.programs, stages.RUNNER, _Runner(text))
    w = window()
    for n in STAGE_READERS:
        assert run.load_module("metrics", n).read(w) is None


def test_an_untraced_window_or_a_program_without_spans_reads_nothing(
        monkeypatch):
    w = window()
    w.reduced = None
    for n in STAGE_READERS:
        assert run.load_module("metrics", n).read(w) is None
    monkeypatch.setattr(stages, "program_spans", lambda: None)
    w = window()
    for n in STAGE_READERS + SETUP_READERS:
        assert run.load_module("metrics", n).read(w) is None


def test_the_chunk_runner_is_attributed_stage_by_stage():
    """The program's own runner (megha, 64 workers, on the CPU): every
    scoped instruction keeps its own stage, each dispatch section and the
    runtime's stages are found, and the set-up readers read the program's
    span and counter."""
    cfg = SimxConfig(num_workers=64, num_gms=2, num_lms=2, group_size=16)
    tasks = export_workload(synthetic_trace(
        num_jobs=6, tasks_per_job=16, load=0.9, num_workers=64, seed=4))
    rule = rt.get_rule("megha")
    step = rule.build_step(cfg, tasks, jax.random.PRNGKey(0))
    runner = engine.make_chunk_runner(step, 4)
    runner(rule.init(cfg, tasks))
    text = runner.hlo_text()
    st = stages.attribute(text)
    hlo = stages.Hlo(text)
    for name, op in hlo.op_name.items():
        if stages.scope_of(op):
            assert st[name] == stages.scope_of(op)
    found = set(st.values())
    assert {"simx.megha.views", "simx.megha.internal", "simx.megha.borrow",
            "simx.megha.head", "simx.complete", "simx.metrics",
            "simx.done"} <= found
    # each listed op once: a window of every instruction sums whole
    w = window(op_s=[(f"%{n}", 1.0) for n in hlo.comp], rounds=4,
               datacenters=1)
    got = stages.reduce_stages(w.reduced.op_s, st)
    assert sum(got.values()) == pytest.approx(len(hlo.comp))
    for n in SETUP_READERS:
        assert run.load_module("metrics", n).read(w) > 0
