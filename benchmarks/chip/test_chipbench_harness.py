"""The harness on the CPU: every cell resolves by name, each driver's unit
of work runs at a tiny size, the entry refuses anything but a TPU, and the
benchmark's traffic and reference agree with the program where they
should."""

import tinycells  # first: the CPU, and the program on the path

import json
import re

import jax.numpy as jnp
import numpy as np
import pytest

import check
import peaks
import ref_megha
import ref_pigeon
import ref_sparrow
import run
import tracegen

BENCH = run.load_json(run.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s",
                                                        "sim_s_per_s"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves(name):
    cell, config, traffic = run.find_cell(name, BENCH)
    conf = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert conf["file"].startswith("benchmarks/chip/configs/")
    assert config["name"] == cell["config"]
    assert config["reduced"] == conf["reduced"]
    assert (run.HERE / "drivers" / f"{traffic['driver']}.py").is_file()
    e2e = run.cell_metrics(name, BENCH, trace=False)
    layer = run.cell_metrics(name, BENCH, trace=True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert callable(run.load_module("metrics", m["name"]).read)


def test_entry_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as ex:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert ex.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


@pytest.mark.parametrize("name", sorted(tinycells.TINY))
def test_a_tiny_run_of_each_cell_is_correct(name):
    out = tinycells.tiny_run(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["sim_s_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_tracegen_matches_the_program_generators():
    from repro.simx.state import export_workload
    from repro.workload import synth

    mine = tracegen.generate({"generator": "synthetic", "num_jobs": 30,
                              "tasks_per_job": 20, "task_duration": 1.0,
                              "load": 0.7, "arrivals": "poisson"}, 300, 2**33)
    theirs = export_workload(synth.synthetic_trace(
        num_jobs=30, tasks_per_job=20, load=0.7, num_workers=300,
        seed=2**33))
    for k, v in mine.items():
        np.testing.assert_array_equal(v, np.asarray(getattr(theirs, k)), k)
    mine = tracegen.generate({"generator": "trace_like", "num_jobs": 200,
                              "total_tasks": 3000, "load": 0.8}, 400, 9)
    theirs = export_workload(synth.google_like_trace(
        num_jobs=200, total_tasks=3000, num_workers=400, seed=9))
    for k, v in mine.items():
        np.testing.assert_array_equal(v, np.asarray(getattr(theirs, k)), k)


def test_a_seed_shuffles_each_job_and_keeps_the_layout():
    """A trace block with its own seed and ``shuffle_within_jobs`` gives
    every run seed the same jobs, arrivals, task counts, job estimates and
    per-job multiset of durations, so the same per-group FIFO widths, with
    the tasks of each job in another order."""
    block = {"generator": "trace_like", "num_jobs": 300, "total_tasks": 6000,
             "load": 0.8, "seed": 1, "shuffle_within_jobs": True}
    a = tracegen.generate(block, 400, 2**31 + 3)
    b = tracegen.generate(block, 400, 2**31 + 4)
    plain = tracegen.generate({**block, "shuffle_within_jobs": False}, 400,
                              2**31 + 3)
    for k in ("job", "submit", "job_submit", "job_ideal", "job_ntasks",
              "job_est"):
        np.testing.assert_array_equal(a[k], b[k], k)
        np.testing.assert_array_equal(a[k], plain[k], k)
    assert (a["duration"] != b["duration"]).any()
    for t in (a, b):
        for j in range(300):
            mine = t["job"] == j
            np.testing.assert_array_equal(
                np.sort(t["duration"][mine]),
                np.sort(plain["duration"][mine]))
    np.testing.assert_array_equal(
        tracegen.generate(block, 400, 2**31 + 3)["duration"], a["duration"])


def test_peaks_refuse_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def _cluster(W):
    return {"num_workers": W, "num_gms": 8, "num_lms": 8,
            "heartbeat_interval": 1.0, "hop": 0.0005, "dt": 0.01}


def _program_megha(trace, W, seed, rounds):
    from repro.simx import runtime
    from repro.simx.state import SimxConfig, TaskArrays

    tasks = TaskArrays(**{k: jnp.asarray(v) for k, v in trace.items()})
    cfg = SimxConfig(num_workers=W, heartbeat_interval=1.0, dt=0.01)
    return runtime.simulate_fixed("megha", cfg, tasks, seed, rounds)


CASES = {
    "megha-512": ("megha", 512, {"generator": "synthetic", "num_jobs": 16,
                                 "tasks_per_job": 96, "task_duration": 1.0,
                                 "load": 0.8}, 3, 400),
    "megha-1024": ("megha", 1024, {"generator": "synthetic", "num_jobs": 16,
                                   "tasks_per_job": 96, "task_duration": 1.0,
                                   "load": 0.95}, 2**31, 400),
    "sparrow": ("sparrow", 256, {"generator": "synthetic", "num_jobs": 40,
                                 "tasks_per_job": 100, "task_duration": 1.0,
                                 "load": 0.99}, 5, 600),
    "sparrow-trace": ("sparrow", 400, {"generator": "trace_like",
                                       "num_jobs": 300, "total_tasks": 6000,
                                       "load": 0.8}, 9, 800),
    "pigeon": ("pigeon", 410, {"generator": "trace_like", "num_jobs": 300,
                               "total_tasks": 6000, "load": 0.8}, 9, 1500),
}
REFS = {"megha": ref_megha, "sparrow": ref_sparrow, "pigeon": ref_pigeon}


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_equals_the_program(case):
    """Each rule's plain re-simulation matches the program's state bit for
    bit after the same rounds from the same trace and scheduler seed."""
    from repro.simx import runtime
    from repro.simx.state import SimxConfig, TaskArrays

    rule, W, gen, seed, rounds = CASES[case]
    seed %= 2**31 - 1
    trace = tracegen.generate(gen, W, 5)
    cl = {"num_workers": W, "num_gms": 8, "num_lms": 8,
          "heartbeat_interval": 1.0, "hop": 0.0005, "dt": 0.01,
          "probe_ratio": 2, "num_distributors": 5, "group_size": 40,
          "reserved_per_group": 2, "wfq_weight": 4, "long_threshold": 10.0}
    tasks = TaskArrays(**{k: jnp.asarray(v) for k, v in trace.items()})
    got = runtime.simulate_fixed(
        rule, SimxConfig(**cl), tasks, seed, rounds,
        match_fn=runtime.default_match_fn(),
        pick_fn=runtime.default_match_fn(block_rows=1))
    ref = REFS[rule].simulate(trace, cl, seed=seed, rounds=rounds)[rounds]
    got = {k: np.asarray(getattr(got, k)) for k in
           ("t", "task_finish", "worker_finish", "worker_task", "messages",
            "inconsistencies")}
    assert np.isfinite(got["task_finish"]).sum() > 500
    assert check.state_gap(got, ref) == 0
    assert float(got["t"]) == float(ref["t"])


def test_check_state_reads_zero_on_a_sound_state_and_catches_breaches():
    trace = tracegen.generate({"generator": "synthetic", "num_jobs": 12,
                               "tasks_per_job": 64, "task_duration": 1.0,
                               "load": 0.9}, 512, 1)
    rounds = 150
    st = _program_megha(trace, 512, 1, rounds)
    s = {k: np.asarray(getattr(st, k)) for k in
         ("t", "rnd", "task_finish", "worker_finish", "worker_task", "lost")}
    kw = dict(rounds=rounds, dt=0.01, hop=0.0005, hops=3, num_workers=512)
    assert check.check_state(s, trace, **kw) == {
        "clock_gap": 0.0, "ledger_gap": 0.0, "timing_errors": 0.0,
        "capacity_excess": 0.0}

    def breach(**change):
        return check.check_state({**s, **change}, trace, **kw)

    done = np.nonzero(s["task_finish"] <= s["t"])[0][0]
    assert breach(task_finish=np.where(
        np.arange(s["task_finish"].size) == done,
        s["task_finish"] + np.float32(1e-3), s["task_finish"]),
    )["timing_errors"] == 1
    assert breach(rnd=rounds - 1)["clock_gap"] >= 1
    assert breach(lost=np.int32(2))["ledger_gap"] == 2
    busy = np.nonzero(s["worker_finish"] > s["t"])[0]
    wt = s["worker_task"].copy()
    wt[busy[1]] = wt[busy[0]]
    assert breach(worker_task=wt)["ledger_gap"] >= 1
    assert breach(**{"t": s["t"] + np.float32(0.01)})["clock_gap"] > 0
    early = s["task_finish"].copy()
    early[-1] = np.float32(0.0015) + trace["duration"][-1]
    assert breach(task_finish=early)["timing_errors"] >= 1
    assert check.check_state(s, trace, **{**kw, "num_workers": 100})[
        "capacity_excess"] > 0


def test_state_gap_compares_the_whole_state_with_the_reference():
    trace = tracegen.generate({"generator": "synthetic", "num_jobs": 12,
                               "tasks_per_job": 64, "task_duration": 1.0,
                               "load": 0.9}, 512, 4)
    st = _program_megha(trace, 512, 6, 200)
    got = {k: np.asarray(getattr(st, k)) for k in
           ("task_finish", "worker_finish", "worker_task", "messages",
            "inconsistencies")}

    def ref(seed):
        return ref_megha.simulate(trace, _cluster(512), seed=seed,
                                  rounds=200)[200]

    assert check.state_gap(got, ref(6)) == 0
    assert check.state_gap(got, ref(7)) > 0


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_relabelled_workers_do_the_same_work_and_match_the_reference(seed):
    """Megha's GM orders relabelled by ``worker_labels`` (each worker kept
    in its LM's partition of its GM) schedule every task in the same round
    as the plain orders, onto the relabelled workers; the reference given
    the same labels equals the program bit for bit."""
    import jax

    from repro.simx import megha, runtime
    from repro.simx.state import SimxConfig, TaskArrays

    W, rounds = 512, 400
    trace = tracegen.generate({"generator": "synthetic", "num_jobs": 16,
                               "tasks_per_job": 96, "task_duration": 1.0,
                               "load": 0.95}, W, 5)
    labels = tracegen.worker_labels(seed, W, 8, 8)
    w = np.arange(W)
    assert sorted(labels) == list(w) and (labels != w).any()
    assert (labels // 64 == w // 64).all() and (labels // 8 == w // 8).all()
    tasks = TaskArrays(**{k: jnp.asarray(v) for k, v in trace.items()})
    cfg = SimxConfig(num_workers=W, heartbeat_interval=1.0, dt=0.01)
    base = megha.gm_orders(jax.random.PRNGKey(1), cfg)
    fields = ("t", "task_finish", "worker_finish", "worker_task", "messages",
              "inconsistencies")

    def program(orders):
        step = megha.make_megha_step(cfg, tasks, orders,
                                     runtime.default_match_fn())
        advance = jax.jit(lambda s: jax.lax.fori_loop(
            0, rounds, lambda i, x: step(x), s))
        st = advance(megha.RULE.init(cfg, tasks))
        return {k: np.asarray(getattr(st, k)) for k in fields}

    plain = program(base)
    got = program(jnp.asarray(labels)[base])
    assert np.isfinite(got["task_finish"]).sum() > 500
    assert got["inconsistencies"] > 0
    np.testing.assert_array_equal(got["task_finish"], plain["task_finish"])
    np.testing.assert_array_equal(got["worker_finish"][labels],
                                  plain["worker_finish"])
    assert got["messages"] == plain["messages"]
    ref = ref_megha.simulate(trace, _cluster(W), seed=1, rounds=rounds,
                             labels=labels)[rounds]
    assert check.state_gap(got, ref) == 0


#: A reading of the reference's work that the compared rounds of each tiny
#: cell must hold.
WORK = {"synth50k.megha": "jobs_done", "synth50k.sparrow": "compacted",
        "google13k.pigeon": "low_launches"}


@pytest.mark.parametrize("name", sorted(WORK))
def test_setup_starts_the_window_and_the_reference_reads_its_work(name):
    import jax

    config, traffic = tinycells.tiny(name)
    drv = run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, 2**31 + 5, jax.devices()[:1])
    drv.warm()
    assert jax.config.jax_enable_compilation_cache
    start = drv.rounds
    assert start >= traffic["start_rounds"] and start % drv.chunk == 0
    assert int(drv.state.rnd) == start and drv.finished == []
    assert drv.unit_s > 0 and not drv.inflight
    drv.ahead = 3
    while drv.rounds < traffic["reference_rounds"] and not drv.finished:
        drv.send()
        assert len(drv.inflight) <= drv.ahead
    drv.drain()
    nums, attempted, failed = drv.verify()
    assert all(v == 0 for v in nums.values()), nums
    assert drv.work["rounds"] > start and drv.work[WORK[name]] > 0


def test_a_completed_trace_drops_the_chunks_sent_past_it_and_starts_again():
    """Chunks in flight past the chunk that completes the trace are not
    counted; the next chunk starts a fresh replay, and the finished one
    is checked."""
    import jax

    config, traffic = tinycells.tiny("synth50k.megha")
    drv = run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, 2**31 + 9, jax.devices()[:1])
    drv.warm()
    drv.ahead = 6
    before = drv.retired
    while not drv.finished:
        drv.send()
    (state, rounds), = drv.finished
    assert bool(np.all(np.asarray(state.task_finish) <= state.t))
    assert int(state.rnd) == rounds and drv.retired - before == (
        rounds - traffic["start_rounds"])
    assert not drv.inflight and drv.rounds == 0 and int(drv.sent.rnd) == 0
    drv.send()
    drv.drain()
    assert drv.rounds == drv.chunk and int(drv.state.rnd) == drv.chunk
    nums, attempted, failed = drv.verify()
    assert all(v == 0 for v in nums.values()), nums
    assert attempted > 0 and failed == 0
