"""The plain reference against the program's pipeline, tick by tick, on the
same drawn rows at a small size, under each delay profile."""
import json

import numpy as np
import pytest

import reference
from conftest import HERE
from generator import Traffic, seed_key
from system import Program, node_lags

MIX = {"arrival_rate": 0.3, "size_choices": [1024, 512],
       "size_probs": [0.5, 0.5], "chunk_ticks": 8}


@pytest.mark.parametrize("name", ["tiny_g1", "tiny_g4"])
@pytest.mark.parametrize("segment", [0, 1])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_reference_equals_program(name, segment, seed):
    dep = json.loads((HERE / "data" / f"{name}.json").read_text())
    T = 40
    profiles = node_lags(dep)
    prog = Program(dep, profiles, None)
    traffic = Traffic(MIX, dep["clients"])
    skey = traffic.segment_key(seed_key(seed), segment)
    state, reads, admitted = prog.init(segment), [], []

    def read():
        _, count, com = prog.committed(state)
        reads.append((int(count), int(com)))

    for t in range(T):
        state, adm = prog.tick(state, *traffic.tick(skey, t))
        admitted.append(int(adm))
        read()
    while reads[-1][1] < int(prog.admitted(state)) and \
            len(reads) < T + dep["drain_ticks_max"]:
        state, adm = prog.tick(state, *prog.no_arrivals)
        admitted.append(int(adm))
        read()
    ref = reference.segment(dep, profiles[segment % len(profiles)],
                            traffic.segment_sizes(skey, T),
                            reference.Routes(dep["disseminators"],
                                             dep["groups"]))
    assert ref["all_committed"] and ref["n_batches"] > 100
    assert reads == list(zip(ref["ordered"], ref["committed"]))
    assert admitted == list(ref["admitted"])
    merged, _, com = prog.committed(state)
    ids = np.asarray(merged)[:int(com)]
    stride, S = dep["admission_capacity"], dep["seq_capacity"]
    np.testing.assert_array_equal(ids // stride, ref["log_group"])
    np.testing.assert_array_equal(ids % stride, ref["log_rank"])
    codes = np.asarray(state.bid_code)[ids // stride, ids % stride]
    np.testing.assert_array_equal(codes, ref["log_lane"] * S
                                  + ref["log_seq"])
    np.testing.assert_array_equal(np.asarray(state.flushed_bytes),
                                  ref["flushed_bytes"])
    np.testing.assert_array_equal(np.asarray(state.n_flushed),
                                  ref["n_flushed"])
