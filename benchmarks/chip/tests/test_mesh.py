"""A deployment's ``mesh_devices`` puts the engine's groups on a device mesh:
a whole run of the harness on four CPU devices is correct and commits the
same log as the unmeshed deployment; a deployment the devices cannot hold
is refused, never run on fewer."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import harness
from conftest import CHIP, HERE, ROOT
from system import Program, node_lags, pipeline_config
from test_faults import bench_for

CELL = "paper_dc_g4.saturated"
SEED = 2**31 + 29


class Recorder(Program):
    """The sound program; keeps each segment's arrival ticks and committed
    log in ``Recorder.logs`` (warm-up's segments first, then the window's)."""

    logs: list[dict] = []

    def init(self, segment: int):
        self.logs.append({"ticks": 0})
        return super().init(segment)

    def run_chunk(self, state, arrived, sizes):
        self.logs[-1]["ticks"] += int(arrived.shape[0])
        return super().run_chunk(state, arrived, sizes)

    def record(self, state, merged, com) -> dict:
        self.logs[-1].update(merged=np.asarray(merged).tolist(),
                             committed=int(com))
        return super().record(state, merged, com)


_CHILD = f"""
import json, sys
sys.path[:0] = [{str(ROOT / 'src')!r}, {str(CHIP)!r}, {str(HERE)!r}]
import jax
import harness
from test_faults import bench_for
from test_mesh import CELL, SEED, Recorder
out = {{"devices": len(jax.devices())}}
for dep in ("tiny_g4", "tiny_g4_mesh"):
    Recorder.logs = []
    r = harness.run_cell(bench_for(dep), CELL, SEED, 1.0, False,
                         program=Recorder, require_tpu=False)
    out[dep] = {{"correct": r["correct"], "checks": r["checks"],
                 "logs": Recorder.logs}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_meshed_run_is_correct(four_device_runs):
    assert four_device_runs["devices"] == 4
    mesh = four_device_runs["tiny_g4_mesh"]
    assert mesh["correct"] is True, mesh["checks"]
    assert four_device_runs["tiny_g4"]["correct"] is True


def test_meshed_log_equals_unmeshed(four_device_runs):
    """Every segment that both runs drove to the same arrival tick, warm-up's
    included, has the same committed log and count; both runs drove at least
    one whole segment under each delay profile."""
    one = four_device_runs["tiny_g4"]["logs"]
    mesh = four_device_runs["tiny_g4_mesh"]["logs"]
    dep = json.loads((HERE / "data" / "tiny_g4.json").read_text())
    n_profiles = len(dep["delay_profiles"])
    same = [(a, b) for a, b in zip(one, mesh) if a["ticks"] == b["ticks"]]
    whole = [a for a, _ in same[n_profiles:]
             if a["ticks"] == dep["segment_ticks"]]
    assert len(whole) >= n_profiles, [s["ticks"] for s in one + mesh]
    for a, b in same:
        assert a["committed"] == b["committed"] > 0
        assert a["merged"] == b["merged"]


@pytest.mark.parametrize("mesh_devices, says", [
    (4, "JAX sees"), (3, "does not divide"), (0, "whole number")])
def test_mesh_devices_not_held_is_refused(mesh_devices, says):
    """On the test process's one device a four-device deployment fails in
    the harness; so does one that splits the groups unevenly."""
    import jax
    assert len(jax.devices()) < 4
    dep = json.loads((HERE / "data" / "tiny_g4_mesh.json").read_text())
    dep["mesh_devices"] = mesh_devices
    with pytest.raises(ValueError, match=f"mesh_devices.*{says}"):
        Program(dep, node_lags(dep), None)
    if mesh_devices == 4:
        with pytest.raises(ValueError, match="mesh_devices"):
            harness.run_cell(bench_for("tiny_g4_mesh"), CELL, SEED, 0.1,
                             False, require_tpu=False)


def test_mesh_devices_sets_only_the_engine_mesh():
    """Without the key the engine has no mesh (the paper's deployment runs
    as before); with it, the mesh is the only field that changes."""
    paper = json.loads((ROOT / "benchmarks/chip/configs/paper_dc_g4.json")
                       .read_text())
    assert pipeline_config(paper, node_lags(paper)[0]).engine.mesh is None
    deps = [json.loads((HERE / "data" / f"{n}.json").read_text())
            for n in ("tiny_g4", "tiny_g4_mesh")]
    one, mesh = (pipeline_config(d, node_lags(d)[0]) for d in deps)
    assert mesh.engine.mesh.n_devices == 4
    assert dataclasses.replace(
        mesh, engine=dataclasses.replace(mesh.engine, mesh=None)) == one
