"""The benchmark's four-chip data-center deployment, at a test size: eight
ordering groups, two to a device on a four-device mesh
(``mesh_devices: 4``), driven whole through the benchmark harness on four
CPU devices.

The run must be ``correct`` against the plain reference
(``benchmarks/chip/reference.py``), and every log segment it shares with
the same deployment run on one device must commit the same log and count
the same at every stage boundary (the sums of ``repro.pipeline.COUNTERS``):
the group-sharded engine, its all-gathers and the partitioner's
collectives in the replicated front stages change where the work runs,
never what it does.

On a mesh a segment's first chunk runs ``run_pipeline``'s executable of a
fresh state, the other chunks that of a state placed as a chunk leaves it.
The trace readers (``scopes.py``, ``collectives.py``) map trace ops to
stages and collectives by the instruction names of the second alone, so
both executables must give every name they share the same stage and the
same collectives.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
CHIP = REPO / "benchmarks" / "chip"
DEPLOYMENT = CHIP / "tests" / "data" / "tiny_g8_mesh4.json"
CELL = "paper_dc_g8_mesh4.saturated"
SEED = 2**32 + 41

_CHILD = """
import json, sys
sys.path[:0] = [{src!r}, {chip!r}]
import jax
import numpy as np
import harness
from repro.pipeline import COUNTERS
from system import Program

class Recorder(Program):
    # keeps each segment's arrival ticks, committed log and counter sums
    logs = []

    def init(self, segment):
        self.logs.append({{"ticks": 0, "counts": dict.fromkeys(COUNTERS, 0)}})
        return super().init(segment)

    def _count(self, out):
        for k in COUNTERS:
            self.logs[-1]["counts"][k] += int(np.asarray(out[k]).sum())

    def run_chunk(self, state, arrived, sizes):
        state, outs = self._run(self.cfg, state, arrived, sizes, self.route)
        self.logs[-1]["ticks"] += int(arrived.shape[0])
        self._count(outs)
        return state, outs["admitted"]

    def tick(self, state, arrived, sizes):
        state, out = self._tick(self.cfg, state, arrived, sizes, self.route)
        self._count(out)
        return state, out["admitted"]

    def record(self, state, merged, com):
        self.logs[-1].update(merged=np.asarray(merged).tolist(),
                             committed=int(com))
        return super().record(state, merged, com)

bench = json.loads(open({bench!r}).read())
out = {{"devices": len(jax.devices())}}
for name, path in {deps!r}.items():
    bench["configs"] = [dict(c, file=path) for c in bench["configs"]]
    Recorder.logs = []
    r = harness.run_cell(bench, {cell!r}, {seed!r}, 1.0, False,
                         program=Recorder, require_tpu=False)
    out[name] = {{"correct": r["correct"], "checks": r["checks"],
                  "logs": Recorder.logs}}

# run_pipeline's two executables on the mesh: a fresh state and a kept one
import collectives, scopes
from generator import Traffic, seed_key
from system import node_lags
bench["configs"] = [dict(c, file={deps!r}["mesh"]) for c in bench["configs"]]
_, dep, mix = harness.load_cell(bench, {cell!r})
prog = Program(dep, node_lags(dep), None)
traffic = Traffic(mix, dep["clients"])
rows = traffic.chunk(traffic.segment_key(seed_key({seed!r}), 0), 0)
fresh = prog.init(0)
kept, _ = prog.run_chunk(fresh, *rows)
texts = [prog._run.lower(prog.cfg, s, *rows, prog.route).compile().as_text()
         for s in (fresh, kept)]
out["executables"] = [{{"scopes": scopes.op_scopes(t),
                        "collectives": collectives.instructions(t)}}
                      for t in texts]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The deployment meshed, and the same file without ``mesh_devices``,
    each one run of the harness in one process with four CPU devices."""
    dep = json.loads(DEPLOYMENT.read_text())
    assert dep["groups"] == 8 and dep["mesh_devices"] == 4
    del dep["mesh_devices"]
    one = tmp_path_factory.mktemp("deployment") / "tiny_g8.json"
    one.write_text(json.dumps(dep))
    child = _CHILD.format(src=str(REPO / "src"), chip=str(CHIP),
                          bench=str(REPO / "BENCHMARK.json"),
                          deps={"mesh": str(DEPLOYMENT), "one": str(one)},
                          cell=CELL, seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_two_groups_a_device_run_is_correct(runs):
    assert runs["devices"] == 4
    assert runs["mesh"]["correct"] is True, runs["mesh"]["checks"]
    assert runs["one"]["correct"] is True, runs["one"]["checks"]


def test_meshed_log_and_counters_equal_one_device(runs):
    """Every segment both runs drove to the same arrival tick, warm-up's
    included, commits the same log and sums every counter the same; both
    runs drove a whole segment under each delay profile."""
    dep = json.loads(DEPLOYMENT.read_text())
    one, mesh = runs["one"]["logs"], runs["mesh"]["logs"]
    same = [(a, b) for a, b in zip(one, mesh) if a["ticks"] == b["ticks"]]
    profiles = len(dep["delay_profiles"])
    whole = [a for a, _ in same[profiles:]
             if a["ticks"] == dep["segment_ticks"]]
    assert len(whole) >= profiles, [s["ticks"] for s in one + mesh]
    for a, b in same:
        assert a["committed"] == b["committed"] > 0
        assert a["merged"] == b["merged"]
        assert a["counts"] == b["counts"]
        assert a["counts"]["decided"] > 0


def test_fresh_and_kept_state_executables_name_ops_alike(runs):
    """Every instruction of the fresh state's executable is named in the
    kept state's, under the same stage, and both hold the same collectives
    under the same names: what the trace readers map by the kept state's
    names holds for a segment's first chunk too."""
    fresh, kept = runs["executables"]
    assert fresh["scopes"].keys() <= kept["scopes"].keys()
    assert {k: kept["scopes"][k] for k in fresh["scopes"]} == fresh["scopes"]
    assert "ht.admission" in fresh["scopes"].values()
    assert fresh["collectives"] == kept["collectives"]
    assert {"all-gather", "all-reduce"} <= set(kept["collectives"].values())
