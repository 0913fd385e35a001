"""Device time of the meshed program's collectives, from the trace of a
``--trace 1`` run.

On a device mesh the compiled ``run_pipeline`` exchanges data between chips:
the engine's ``all_gather``s (``engine/meshed.py``) and the collectives the
partitioner adds itself, such as an ``all-reduce`` inside the replicated
admission stage. No stage scope can name those, so they are told apart by
their opcode: a trace op is a collective where its instruction in the
compiled module's text is one of ``COLLECTIVES`` (an async ``-start`` or
``-done`` counts as an op of its own; the module compiled for a v5e host has
none). ``reduce`` gives the union of those ops' intervals among
``run_pipeline``'s ops in the traced window, averaged over the device
planes; the commit gate's collectives count in ``commit_gate_dev_ms.rps``.

The names come from the executable of the kept state, the one
``scopes.compiled_scopes`` reads; a segment's first chunk runs the
executable of a fresh state, which names its ops alike
(``tests/test_mesh_deployment.py``). Where the compiled module has
collectives but the traced window shows none of them, the trace names its
ops otherwise and a 0 would hide it, so ``reduce`` raises. A program on one
device has no collective and reads 0.
"""
from __future__ import annotations

import bisect

import devtrace
import scopes
from harness import TRACE_DIR

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def instructions(hlo_text: str) -> dict[str, str]:
    """Instruction name → opcode, for the collectives of a compiled
    module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = scopes._INSTR.match(line)
        code = m and scopes._OPCODE.search(m.group(2))
        if not code:
            continue
        opcode = code.group(1)
        if opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES:
            out[m.group(1)] = opcode
    return out


def reduce(ev: dict, table: dict[str, str]) -> float | None:
    """Device seconds of the collectives (``table``) of ``run_pipeline``'s
    module in the traced window, averaged over the device planes; ``ev`` as
    ``scopes.events`` gives it. None where the trace has no window or no
    device op inside it."""
    windows = [(s, e) for name, s, e in ev["host"] if name == devtrace.WINDOW]
    if not windows or not ev["device"]:
        return None
    lo, hi = windows[-1]
    total, seen, planes = 0.0, 0, 0
    for plane, ops in sorted(ev["device"].items()):
        inside = [(op, s, e) for op, s, e in ops if min(e, hi) > max(s, lo)]
        if not inside:
            continue
        planes += 1
        mods = sorted(ev["modules"].get(plane, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        mine = []
        for op, s, e in inside:
            i = bisect.bisect_right(starts, s) - 1
            if (op in table and i >= 0 and s < mods[i][2]
                    and mods[i][0] == scopes.PIPELINE):
                mine.append((s, e))
        seen += len(mine)
        total += sum(e - s for s, e in devtrace.union(mine, lo, hi))
    if not planes:
        return None
    if table and not seen:
        raise RuntimeError(
            f"run_pipeline's compiled module has collectives "
            f"({', '.join(sorted(table))}) but the traced window shows none "
            f"of them on {planes} plane(s)")
    return total / 1e9 / planes


def compiled_table(run) -> dict[str, str]:
    """``instructions`` of the traced segment's ``run_pipeline``, from the
    executable the run used on the kept state."""
    prog = run.program
    rows = run.traffic.chunk(run.segment_key, 0)
    low = prog._run.lower(prog.cfg, run.state, *rows, prog.route)
    return instructions(low.compile().as_text())


def ms_per_tick(run) -> float | None:
    """Device ms of the collectives per arrival tick of the traced segment;
    None without a trace."""
    if run.trace is None:
        return None
    got = reduce(scopes.events(TRACE_DIR), compiled_table(run))
    return None if got is None else got * 1e3 / run.next_tick
