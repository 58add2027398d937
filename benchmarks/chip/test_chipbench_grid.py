"""The grid driver on the CPU, at a tiny stand-in for
``synth10k.grid.megha.x4``: 512 workers, 2 loads x 2 seeds of 12 jobs x
64 tasks, on one device.  A run is correct on all five numbers, its
reference reads borrow rounds, a fault planted in one point shows, and
the control comes out not correct."""

import tinycells  # first: the CPU, and the program on the path

import jax
import pytest

import run

CELL = "synth10k.grid.megha.x4"
SEED = 2**31 + 77


def tiny() -> tuple[dict, dict]:
    _, config, traffic = run.find_cell(
        CELL, run.load_json(run.ROOT / "BENCHMARK.json"))
    config["cluster"]["num_workers"] = 512
    config["trace"].update(num_jobs=12, tasks_per_job=64, loads=[0.5, 0.8])
    traffic["seeds_per_load"] = 2
    return config, traffic


def tiny_run(program_cluster=None) -> dict:
    import time

    config, traffic = tiny()
    metrics = run.cell_metrics(
        CELL, run.load_json(run.ROOT / "BENCHMARK.json"), False)
    return run.run_cell(config, traffic, metrics, seed=SEED, seconds=0.3,
                        trace=False, devices=jax.devices()[:1],
                        program_cluster=program_cluster,
                        t0=time.perf_counter())


def driver():
    config, traffic = tiny()
    drv = run.load_module("drivers", traffic["driver"]).Driver(
        config, traffic, SEED, jax.devices()[:1])
    drv.warm()
    while drv.kept is None:
        drv.send()
    drv.drain()
    return drv


def test_a_tiny_grid_run_is_correct_on_all_five_numbers():
    out = tiny_run()
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"clock_gap", "ledger_gap", "timing_errors",
                                  "capacity_excess", "ref_state_gap"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["sim_s_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0


def test_the_reference_reads_every_point_and_borrow_rounds():
    drv = driver()
    assert drv.datacenters == 4
    assert drv.seeds == [SEED % (2**31 - 1), (SEED + 1) % (2**31 - 1)]
    nums, attempted, failed = drv.verify()
    assert all(v == 0 for v in nums.values()), nums
    work = drv.work
    assert work["rounds"] >= 192 and set(work) == {"rounds", "0.5", "0.8"}
    for load in ("0.5", "0.8"):
        assert len(work[load]["borrow_rounds"]) == 2
        assert min(work[load]["tasks_done"]) > 0
    assert max(work["0.8"]["borrow_rounds"]) >= 1


@pytest.mark.parametrize("point", [0, 3], ids=["first", "last"])
def test_a_fault_planted_in_one_point_shows(point):
    """The latest finish time of one point's workers moved in the compared
    state: the reference sees that point differ."""
    import numpy as np

    drv = driver()
    state, rounds = drv.kept
    w = int(np.argmax(np.asarray(state.worker_finish)[0, point]))
    drv.kept = (state.replace(worker_finish=state.worker_finish.at[
        0, point, w].add(0.25)), rounds)
    nums, _, _ = drv.verify()
    assert nums["ref_state_gap"] > 0


def test_the_control_is_not_correct():
    """The program on twice the stated ``dt``, held by the reference to
    the stated one."""
    config, _ = tiny()
    out = tiny_run({**config["cluster"], "dt": 2 * config["cluster"]["dt"]})
    assert not out["correct"]
    assert out["checks"]["ref_state_gap"]["value"] > 0
