"""The stage reduction of a device trace (``scopes.py``): the scope rules on
a hand-written module text, the reduction on hand-made events, the scopes
of the program compiled here, and a sample recorded from a TPU v5e trace
of the saturated cell."""
import json

import pytest

import devtrace
import scopes
from conftest import HERE
from generator import Traffic, seed_key
from harness import Run
from system import Program, node_lags

HLO = """
%fused_computation.1 (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %neg.0 = s32[4]{0} negate(%p), metadata={op_name="jit(f)/ht.b/neg"}
}

ENTRY %main (a: s32[4]) -> (s32[4], s32[4], s32[]) {
  %a = s32[4]{0:T(128)} parameter(0)
  %fusion.1 = s32[4]{0:T(128)} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/ht.a/jit(g)/ht.b/neg" stack_frame_id=3}
  %copy.2 = s32[4]{0:T(128)S(1)} copy(%fusion.1), metadata={op_name="reduce_window_sum" stack_frame_id=4}
  %bitcast.3 = s32[4]{0} bitcast(%copy.2)
  %fusion.4 = (s32[]{:T(128)}, s32[4]{0}) fusion(%bitcast.3), kind=kLoop, calls=%c, metadata={op_name="jit(f)/while/body/ht.c/reduce_sum"}
  %get-tuple-element.5 = s32[4]{0} get-tuple-element(%fusion.4), index=1, metadata={op_name="jit(f)/while/body/reduce_sum"}
  %get-tuple-element.6 = s32[] get-tuple-element(%fusion.4), index=0, metadata={op_name="jit(f)/while/body/reduce_sum"}
  %add.7 = s32[4]{0} add(%get-tuple-element.5, %a), metadata={op_name="jit(f)/while/body/add"}
  %copy.8 = s32[4]{0} copy(%a)
  ROOT %tuple.9 = (s32[4]{0}, s32[4]{0}, s32[]) tuple(%add.7, %copy.8, %get-tuple-element.6)
}
"""


def test_scope_rules_on_a_module_text():
    got = scopes.op_scopes(HLO)
    assert got["neg.0"] == "ht.b"
    assert got["fusion.1"] == "ht.b"           # the innermost of two
    # no path from the entry: the nearest consumer's stage, through the
    # bitcast and past consumers that have none
    assert got["copy.2"] == "ht.c"
    assert got["bitcast.3"] == "ht.c"
    assert got["fusion.4"] == "ht.c"
    assert got["add.7"] == "unscoped"          # a path, but no stage
    assert got["copy.8"] == "unscoped"         # no metadata, no consumer
    assert got["get-tuple-element.5"] == "unscoped"
    assert scopes.innermost("jit(f)/while/body/closed_call/add") == \
        "unscoped"
    assert scopes.op_name("%fusion.133 = s32[4]{0} fusion(%a)") == \
        "fusion.133"
    assert scopes.module_name("jit_run_pipeline(1437)") == \
        "jit_run_pipeline"


def test_stages_by_hand():
    plane = "/device:TPU:0"
    ev = {"device": {plane: [("while.0", 0, 50), ("fusion.1", 5, 20),
                             ("copy.2", 20, 30), ("fusion.4", 30, 50),
                             ("fusion.9", 60, 70), ("fusion.1", 95, 120),
                             ("fusion.x", 130, 140)]},
          "modules": {plane: [("jit_a", 0, 55), ("jit_b", 58, 90),
                              ("jit_a", 92, 125)]},
          "host": [(devtrace.WINDOW, 0, 135), ("bench.sync", 0, 135)]}
    named = {"jit_a": {"fusion.1": "ht.b", "copy.2": "ht.c",
                       "fusion.4": "unscoped", "while.0": "unscoped"},
             "jit_b": {"fusion.9": "ht.gate"}}
    r = scopes.reduce(ev, named)
    ns = 1e-9
    # the while holds other ops, so only its body's ops count; the last
    # fusion.1 is clipped at neither end; fusion.x runs in no module
    assert r["stages"]["jit_a"] == pytest.approx(
        {"ht.b": 40 * ns, "ht.c": 10 * ns, "unscoped": 20 * ns})
    assert r["stages"]["jit_b"] == pytest.approx({"ht.gate": 10 * ns})
    assert r["stages"]["none"] == pytest.approx({"unscoped": 5 * ns})
    assert r["runs"] == {"jit_a": 2, "jit_b": 1}


def test_no_window_reads_nothing():
    ev = {"device": {"/device:TPU:0": [("a", 0, 5)]}, "modules": {},
          "host": []}
    assert scopes.reduce(ev, {}) is None


MIX = {"arrival_rate": 0.3, "size_choices": [1024, 512],
       "size_probs": [0.5, 0.5], "chunk_ticks": 8}


def test_compiled_pipeline_names_every_stage():
    """The scopes ``read`` finds in the ``run_pipeline`` a run compiled:
    every stage but the commit gate, which is a program of its own and
    names its one scope."""
    dep = json.loads((HERE / "data" / "tiny_g4.json").read_text())
    prog = Program(dep, node_lags(dep), None)
    traffic = Traffic(MIX, dep["clients"])
    skey = traffic.segment_key(seed_key(5), 0)
    state = prog.init(0)
    state, _ = prog.run_chunk(state, *traffic.chunk(skey, 0))
    run = Run(program=prog, traffic=traffic, segment_key=skey, state=state)
    got = scopes.compiled_scopes(run)
    stages = {"ht.gather", "ht.batcher", "ht.admission", "ht.lag_tiles",
              "ht.stability", "ht.ordering", "ht.merge_append",
              "ht.recycle"}
    assert set(got) == {scopes.PIPELINE, scopes.GATE}
    assert set(got[scopes.PIPELINE].values()) == stages | {"unscoped"}
    assert scopes.COMMIT_GATE in set(got[scopes.GATE].values()) <= \
        {scopes.COMMIT_GATE, "unscoped"}


@pytest.mark.parametrize("scoped", [True, False])
def test_gate_time_by_scope_else_by_module(scoped):
    """The gate's ops under its scope per run; where the executable names
    no scope (cached without its metadata), every op of the module."""
    gate = {"ht.commit_gate": 0.006, "unscoped": 0.002} if scoped \
        else {"unscoped": 0.008}
    run = Run(stage_trace={"stages": {scopes.GATE: gate},
                           "runs": {scopes.GATE: 2}})
    want = 3.0 if scoped else 4.0
    assert scopes.gate_ms_per_read(run) == pytest.approx(want)
    assert scopes.gate_ms_per_read(Run(stage_trace=None)) is None


def test_recorded_scoped_sample():
    """A TPU v5e trace of the saturated cell: the end of an arrival chunk
    and the first commit-gate read of the drain. Every leaf op's time is
    counted once, under its module and stage; the gate is one module
    run."""
    sample = json.loads((HERE / "data" / "scoped_trace_sample.json")
                        .read_text())
    r = scopes.reduce(sample, sample["scopes"])
    lo, hi = [(s, e) for n, s, e in sample["host"]
              if n == devtrace.WINDOW][-1]
    (ops,) = sample["device"].values()
    leaf_s = sum(min(e, hi) - max(s, lo) for _, s, e in devtrace.leaves(ops)
                 if min(e, hi) > max(s, lo)) / 1e9
    total = sum(v for per in r["stages"].values() for v in per.values())
    assert total == pytest.approx(leaf_s, rel=1e-12)
    assert r["runs"][scopes.GATE] == 1
    (lo_g, hi_g), = [(s, e) for m, s, e in
                     sample["modules"]["/device:TPU:0"] if m == scopes.GATE]
    gate_s = sum(e - s for _, s, e in devtrace.leaves(ops)
                 if lo_g <= s and e <= hi_g) / 1e9
    assert gate_s > 0
    assert sum(r["stages"][scopes.GATE].values()) == \
        pytest.approx(gate_s, rel=1e-12)
    pipe = r["stages"][scopes.PIPELINE]
    assert set(pipe) - {"unscoped"} and \
        all(k.startswith("ht.") or k == "unscoped" for k in pipe)
