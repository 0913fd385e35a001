"""The collective reduction of a device trace (``collectives.py``): opcodes
from module text, the reduction on hand-made events, and the collectives of
the program compiled here on one device and on four."""
import json
import os
import subprocess
import sys

import pytest

import collectives
import devtrace
from conftest import CHIP, HERE, ROOT
from generator import Traffic, seed_key
from harness import Run
from system import Program, node_lags

# lines of the meshed run_pipeline compiled for a TPU v5e 2x2 host, and an
# async pair and a permute
HLO = """
ENTRY %main (a: s32[4]) -> s32[4] {
  %all-reduce.17 = (s32[202000,1]{0,1:T(1,128)S(1)}, s32[202000]{0:T(1024)S(1)}) all-reduce(%get-tuple-element.1465, %get-tuple-element.1466), channel_id=5, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.3.clone, metadata={op_name="jit(run_pipeline)/while/body/closed_call/ht.admission/jit(take_along_axis)/gather" stack_frame_id=49}
  %get-tuple-element.1468 = s32[202000]{0:T(1024)S(1)} get-tuple-element(%all-reduce.17), index=1
  %all-gather.7 = s32[4,2,512]{2,1,0:T(2,128)S(1)} all-gather(%copy-done.58), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, use_global_device_ids=true, frontend_attributes={async_collective_name="all-gather-start"}
  %copy-start.3 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%p)
  %copy-done.3 = s32[8]{0} copy-done(%copy-start.3)
  %all-gather-start.2 = (s32[2,512]{1,0}, s32[8,512]{1,0}) all-gather-start(%p), channel_id=2, dimensions={0}
  %all-gather-done.2 = s32[8,512]{1,0} all-gather-done(%all-gather-start.2)
  %collective-permute.1 = bf16[16,128]{1,0:T(8,128)(2,1)} collective-permute(%x), source_target_pairs={{0,1}}
  %fusion.3 = s32[4]{0:T(128)} fusion(%all-gather.7), kind=kLoop, calls=%all-gather.7.fused
  ROOT %copy.4 = s32[4]{0} copy(%fusion.3)
}
"""


def test_opcodes_from_module_text():
    assert collectives.instructions(HLO) == {
        "all-reduce.17": "all-reduce", "all-gather.7": "all-gather",
        "all-gather-start.2": "all-gather-start",
        "all-gather-done.2": "all-gather-done",
        "collective-permute.1": "collective-permute"}


PLANES = ("/device:TPU:0", "/device:TPU:1")


def _events(ops_by_plane, window=(0, 200)):
    return {"device": dict(zip(PLANES, ops_by_plane)),
            "modules": {p: [("jit_run_pipeline", 0, 150),
                            ("jit_committed", 160, 200)] for p in PLANES},
            "host": [(devtrace.WINDOW, *window), ("bench.sync", 0, 200)]}


TABLE = {"all-reduce.1": "all-reduce", "ag-start": "all-gather-start",
         "ag-done": "all-gather-done"}


def test_collectives_by_hand():
    """Plane 0: an all-reduce of 10 ns under compute that does not count,
    an async pair whose two ops take 2 and 5 ns, and the gate's all-reduce,
    not counted; plane 1: the all-reduce alone."""
    zero = [("while.0", 0, 150), ("fusion.1", 0, 20),
            ("all-reduce.1", 20, 30), ("fusion.2", 25, 40),
            ("ag-start", 60, 62), ("fusion.3", 65, 80), ("ag-done", 85, 90),
            ("all-reduce.1", 170, 180)]
    one = [("fusion.1", 0, 20), ("all-reduce.1", 20, 30)]
    got = collectives.reduce(_events([zero, one]), TABLE)
    assert got == pytest.approx((17 + 10) / 2 * 1e-9)


def test_window_clips():
    ops = [("all-reduce.1", 5, 15), ("all-reduce.1", 95, 105)]
    got = collectives.reduce(_events([ops, ops], window=(10, 100)), TABLE)
    assert got == pytest.approx(10e-9)


def test_no_window_reads_nothing():
    ev = _events([[("fusion.1", 0, 5)], []])
    ev["host"] = []
    assert collectives.reduce(ev, TABLE) is None


def test_compiled_collectives_the_trace_never_shows_raise():
    """A meshed module whose collectives the window does not show: the
    trace names its ops otherwise, and a 0 would hide it."""
    ops = [("fusion.1", 0, 20), ("all-reduce-renamed.1", 20, 30)]
    with pytest.raises(RuntimeError, match="all-reduce.1"):
        collectives.reduce(_events([ops, ops]), TABLE)


MIX = {"arrival_rate": 0.3, "size_choices": [1024, 512],
       "size_probs": [0.5, 0.5], "chunk_ticks": 8}


def _run(dep_name):
    dep = json.loads((HERE / "data" / f"{dep_name}.json").read_text())
    prog = Program(dep, node_lags(dep), None)
    traffic = Traffic(MIX, dep["clients"])
    skey = traffic.segment_key(seed_key(5), 0)
    state = prog.init(0)
    state, _ = prog.run_chunk(state, *traffic.chunk(skey, 0))
    return Run(program=prog, traffic=traffic, segment_key=skey, state=state,
               next_tick=8)


def test_one_device_program_reads_zero():
    """The unmeshed program has no collective: its ops read 0 ms, though
    the plane is busy, and nothing raises."""
    table = collectives.compiled_table(_run("tiny_g4"))
    assert table == {}
    ops = [("fusion.1", 0, 20), ("all-reduce.1", 20, 30)]
    assert collectives.reduce(_events([ops, ops]), table) == 0.0


_CHILD = f"""
import json, sys
sys.path[:0] = [{str(ROOT / 'src')!r}, {str(CHIP)!r}, {str(HERE)!r}]
import collectives
from test_collectives import _run
print(json.dumps(collectives.compiled_table(_run("tiny_g8_mesh4"))))
"""


def test_meshed_program_has_its_collectives():
    """On four CPU devices, two groups each, the compiled run_pipeline holds
    the engine's all-gathers and the admission's all-reduce."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    table = json.loads(proc.stdout.splitlines()[-1])
    assert {"all-gather", "all-reduce"} <= set(table.values())
