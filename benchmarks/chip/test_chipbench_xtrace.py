"""Trace reduction on a hand-built trace and on a trace recorded here."""

import tinycells  # noqa: F401  first: the CPU, and the program on the path

import jax
import jax.numpy as jnp
import pytest

import xtrace
from xtrace import Event


def test_reduce_hand_built_trace():
    host = [Event("window", 0, 100), Event("chunk", 0, 40),
            Event("reinit", 40, 20), Event("chunk", 60, 40)]
    devices = {
        "/device:TPU:0": [Event("%fusion.1", 5, 20, op=True),
                          Event("%sort.2", 20, 15, op=True),
                          Event("%while.3", 5, 30, op=True),
                          Event("%cond.4", 20, 15, op=True),
                          Event("jit_run", 4, 2),
                          Event("%fusion.1", 70, 20, op=True),
                          Event("%outside", 120, 5, op=True)],
        "/device:TPU:1": [Event("%fusion.1", 0, 50, op=True)],
    }
    red = xtrace.reduce(devices, host, labels=("chunk", "reinit"))
    assert red.window_s == pytest.approx(100e-9)
    # TPU:0 is busy on [4, 35) and [70, 90); TPU:1 on [0, 50)
    assert red.busy_s["/device:TPU:0"] == pytest.approx(51e-9)
    assert red.busy_s["/device:TPU:1"] == pytest.approx(50e-9)
    assert red.busy_mean_s == pytest.approx(50.5e-9)
    # the loop and the conditional hold the ops listed inside them; a
    # module is no op
    assert dict(red.op_s) == pytest.approx(
        {"%fusion.1": 90e-9, "%sort.2": 15e-9})
    # idle on TPU:0: [0,4) chunk, [35,70) mostly reinit, [90,100) chunk
    assert red.gaps[0] == ("reinit", pytest.approx(35e-9))
    assert sorted(n for n, _ in red.gaps) == ["chunk", "chunk", "reinit"]


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        xtrace.reduce({}, [Event("chunk", 0, 1)])


def test_union_and_gaps_clip_to_the_window():
    merged = xtrace.union([(0, 10), (5, 20), (30, 40), (45, 60)], 2, 50)
    assert merged == [(2, 20), (30, 40), (45, 50)]
    assert xtrace.gaps(merged, 0, 55) == [(0, 2), (20, 30), (40, 45),
                                          (50, 55)]


def test_read_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sort(x * 2.0))
    x = jnp.arange(4096.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("chunk"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, host = xtrace.read(xtrace.find_xplane(str(tmp_path)))
    names = [e.name for e in host]
    assert names.count("window") == 1 and names.count("chunk") == 2
    red = xtrace.reduce(devices, host, labels=("chunk",))
    assert red.window_s > 0
    # the CPU backend has no device plane: nothing is busy, all is a gap
    assert devices == {} and red.busy_s == {}
    assert sum(s for _, s in red.gaps) == pytest.approx(red.window_s)
