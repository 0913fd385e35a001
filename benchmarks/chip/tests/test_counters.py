"""The program's boundary counts of a segment, as ``counters.py`` replays
them, against the plain reference on the same rows, under each delay
profile."""
import json

import numpy as np
import pytest

import counters
import reference
from conftest import HERE
from generator import Traffic, seed_key
from harness import Run
from system import Program, node_lags

MIX = {"arrival_rate": 0.3, "size_choices": [1024, 512],
       "size_probs": [0.5, 0.5], "chunk_ticks": 8}


@pytest.mark.parametrize("segment", [0, 1])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_counts_equal_the_reference(segment, seed):
    dep = json.loads((HERE / "data" / "tiny_g4.json").read_text())
    T = 40
    profiles = node_lags(dep)
    prog = Program(dep, profiles, None)
    prog.init(segment)
    traffic = Traffic(MIX, dep["clients"])
    skey = traffic.segment_key(seed_key(seed), segment)
    run = Run(program=prog, traffic=traffic, dep=dep, segment_key=skey,
              next_tick=T)
    got = counters.read(run)
    assert counters.read(run) is got                 # replayed once
    assert got["profile"] == dep["delay_profiles"][segment]["name"]
    ref = reference.segment(dep, profiles[segment % len(profiles)],
                            traffic.segment_sizes(skey, T),
                            reference.Routes(dep["disseminators"],
                                             dep["groups"]))
    assert ref["all_committed"] and ref["n_batches"] > 100
    c = got["counts"]
    assert len(c["admitted"]) == ref["ticks"] == T + got["reads"] - 1
    np.testing.assert_array_equal(c["admitted"], ref["admitted"])
    np.testing.assert_array_equal(c["flushed"], ref["admitted"])
    np.testing.assert_array_equal(np.cumsum(c["ordered"]), ref["ordered"])
    assert c["requests"].sum() == ref["requests"].sum()
    assert c["decided"].sum() == c["stable"].sum() == ref["n_batches"]
    assert (np.cumsum(c["decided"]) <= np.cumsum(c["ordered"])).all()
    assert (c["dropped"] == 0).all()
    admitted = np.cumsum(ref["admitted"])
    assert counters.mean_wait(run, "ordered") == pytest.approx(
        (admitted - ref["ordered"]).sum() / admitted[-1], rel=1e-12)
    assert counters.mean_wait(run, "decided") > \
        counters.mean_wait(run, "ordered") > 0
    # votes count only on stable batches: decided no sooner than stable
    assert counters.mean_wait(run, "decided") >= \
        counters.mean_wait(run, "stable") > 0


def test_window_compiles_counts_a_retrace():
    """After warm-up the count stays where it was through more calls of
    the same shapes, and a call with a new chunk length adds one."""
    import harness
    dep = json.loads((HERE / "data" / "tiny_g4.json").read_text())
    prog = Program(dep, node_lags(dep), None)
    traffic = Traffic(MIX, dep["clients"])
    window = harness.Window(prog, traffic, dep, seed_key(9))
    harness.warm_up(window)
    reader = harness.load_module(harness.HERE / "metrics"
                                 / "window_compiles.rps.py")
    run = Run(program=prog)
    before = reader.read(run)
    window.drive(0.0, max_ticks=2 * MIX["chunk_ticks"])
    assert reader.read(run) == before
    arrived, sizes = traffic.chunk(traffic.segment_key(seed_key(9), 0), 0)
    prog.run_chunk(prog.init(0), arrived[:3], sizes[:3])
    assert reader.read(run) == before + 1
