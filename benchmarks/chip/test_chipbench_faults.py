"""Each fault a cell can have, planted under the timed path of a tiny run
on the CPU, makes ``correct`` come out false; so does the control, the
program run on a round twice the stated ``dt``.

A fixed-trace cell runs one datacenter on one chip, so it can have two of
the faults: a step that returns its state unchanged, and an answer
altered where it is produced."""

import tinycells  # first: the CPU, and the program on the path

import jax.numpy as jnp
import pytest


FIXED = ["synth50k.megha", "synth50k.sparrow", "google13k.pigeon"]


def unchanged(monkeypatch):
    from repro.simx import engine, runtime

    monkeypatch.setattr(runtime, "scan_rounds", lambda step, s, n: s)
    monkeypatch.setattr(engine, "scan_rounds", lambda step, s, n: s)


def altered_finish(monkeypatch):
    """The chunk runner hands back task 0's finish time a millisecond late
    once it has launched."""
    from repro.simx import engine

    make = engine.make_chunk_runner

    def make_altered(step, chunk=256, donate=False):
        runner = make(step, chunk, donate)

        def run(c):
            c, done = runner(c)
            tf = c.task_finish
            bump = jnp.where(jnp.isfinite(tf[0]), 1e-3, 0.0)
            return c.replace(task_finish=tf.at[0].add(bump)), done
        return run

    monkeypatch.setattr(engine, "make_chunk_runner", make_altered)


@pytest.mark.parametrize("fault", [unchanged, altered_finish])
@pytest.mark.parametrize("name", FIXED)
def test_a_fixed_cell_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    out = tinycells.tiny_run(name)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name", FIXED)
def test_the_control_is_not_correct(name):
    config, _ = tinycells.tiny(name)
    coarse = {**config["cluster"], "dt": 2 * config["cluster"]["dt"]}
    out = tinycells.tiny_run(name, program_cluster=coarse)
    assert not out["correct"], out["checks"]
