"""A whole run of the harness on the CPU, past its look for a chip, with the
timed path broken underneath: ``correct`` has to come out false for each
fault a one-chip cell can have, and true for the sound program. (The cell
runs on one chip, so there is no exchange between chips to leave out.)"""
import json

import jax.numpy as jnp
import pytest

import harness
from conftest import ROOT
from control import Ungated
from system import Program


class Stuck(Program):
    """Every step returns its state unchanged."""

    def tick(self, state, arrived, sizes):
        return state, jnp.int32(0)

    def run_chunk(self, state, arrived, sizes):
        return state, jnp.zeros((arrived.shape[0],), jnp.int32)


class HalfBatch(Program):
    """Half of each tick's clients are left out before the program."""

    def _half(self, arrived):
        keep = jnp.arange(arrived.shape[-1]) % 2 == 0
        return arrived & keep

    def tick(self, state, arrived, sizes):
        return super().tick(state, self._half(arrived), sizes)

    def run_chunk(self, state, arrived, sizes):
        return super().run_chunk(state, self._half(arrived), sizes)


class AlteredAnswer(Program):
    """The first id of each segment's committed log is altered where the
    commit gate produces it."""

    def committed(self, state):
        merged, count, com = super().committed(state)
        return merged.at[0].add(1), count, com


def bench_for(dep_file: str) -> dict:
    """``BENCHMARK.json`` with every configuration pointed at a test-size
    deployment."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(c, file=f"benchmarks/chip/tests/data/"
                                     f"{dep_file}.json")
                        for c in bench["configs"]]
    return bench


CELLS = ["paper_dc_g4.saturated"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("program, correct", [
    (Program, True), (Stuck, False), (HalfBatch, False),
    (AlteredAnswer, False), (Ungated, False)])
def test_fault_makes_run_incorrect(cell, program, correct):
    r = harness.run_cell(bench_for("tiny_g4"), cell, 2**31 + 17, 0.3,
                         False, program=program, require_tpu=False)
    assert r["correct"] is correct, r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_its_metrics(cell):
    r = harness.run_cell(bench_for("tiny_g1"), cell, 9, 0.3, True,
                         require_tpu=False)
    assert r["correct"]
    _, layer = harness.cell_metrics(bench_for("tiny_g1"), cell)
    # the CPU trace has no device plane, so only the device share is absent
    want = {m["name"] for m in layer if m["source"] != "device_trace"}
    assert want <= set(r["metrics"])
