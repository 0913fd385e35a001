"""Each control (one guarantee broken in the reference put in the program's
place) must come out not correct, and the sound reference correct, at a
test size."""
import json

import pytest

import compare
import control
import reference
from conftest import HERE
from generator import Traffic, seed_key
from system import node_lags

MIX = {"arrival_rate": 0.1, "size_choices": [1024, 512],
       "size_probs": [0.5, 0.5], "chunk_ticks": 32}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_controls_fail_sound_passes(seed):
    dep = json.loads((HERE / "data" / "tiny_g4.json").read_text())
    traffic = Traffic(MIX, dep["clients"])
    key = seed_key(seed)
    segs = [traffic.segment_sizes(traffic.segment_key(key, s),
                                  dep["segment_ticks"]) for s in range(2)]
    r = control.readings(dep, node_lags(dep), segs,
                         reference.Routes(dep["disseminators"],
                                          dep["groups"]))
    assert compare.passed(r["sound"])
    for name in control.CONTROLS:
        assert not compare.passed(r[name]), name
    assert r["order_quorum_1"]["ordered_reads"]["value"] > 0
    for name in ("stability_quorum_1", "commit_quorum_1", "ungated"):
        assert r[name]["commit_reads"]["value"] > 0, name
