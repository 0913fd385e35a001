"""Device time of the program's stages, from the trace of a ``--trace 1`` run.

The program runs each stage of its tick inside ``jax.named_scope``, with
names that start with ``ht.`` (``ht.gather``, ``ht.merge_append``, ...).
A TPU trace names each device op by its HLO instruction (``%fusion.133 =
...``) and shows the program run it belongs to as an event of the
``XLA Modules`` line, but it carries no scope. The scope comes from the
compiled program: the ``op_name`` metadata of the instruction of the same
name in the module's ``as_text()``. An op whose ``op_name`` holds several
stages counts for the innermost; an op under none is ``unscoped``. An op
whose ``op_name`` has no path from the module's entry (JAX lowers a
cumulative sum out of line, so its ops carry only ``reduce_window_sum``)
or that has no metadata (a copy or bitcast XLA added) counts for the
stage of the nearest op that consumes its result.

``events(profile_dir)`` reads the trace (device ops, module runs and the
host's ``bench.`` annotations); ``reduce(ev, scopes)`` works on those lists
alone, so a test can feed it hand-made events. ``read(run)`` does both for
a metric reader, once per run, and returns None where the program names no
stage (a program without scopes has nothing to read).
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path

import devtrace
from harness import TRACE_DIR

PREFIX = "ht."
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
PIPELINE = "jit_run_pipeline"     # run_pipeline: the chunks of arrival ticks
GATE = "jit_committed"            # the commit gate: one run per read
COMMIT_GATE = "ht.commit_gate"

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r'%([\w.\-]+)')
_OPCODE = re.compile(r'(?<![\w.])([a-z][a-z0-9-]*)\(')
# ops that only rename or regroup values: their metadata is a producer's
STRUCTURAL = {"get-tuple-element", "bitcast", "tuple"}
_OP = re.compile(r'^%?([\w.\-]+) = ')


def innermost(op_name: str) -> str:
    """The last path component of ``op_name`` that is a stage, else
    ``unscoped``."""
    stages = [p for p in op_name.split("/") if p.startswith(PREFIX)]
    return stages[-1] if stages else UNSCOPED


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name → innermost stage (or ``unscoped``), for every
    instruction of a compiled module's text. An instruction without a
    path from the entry (``jit(...)/...``) takes the stage of its nearest
    consumer that names one, breadth first through consumers without a
    path (a multi-output fusion has no metadata of its own) and through
    ``STRUCTURAL`` ops, whose metadata names a producer."""
    stage: dict[str, str | None] = {}
    users: dict[str, list[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        meta = _OP_NAME.search(rest)
        path = meta.group(1) if meta else ""
        code = _OPCODE.search(rest)
        named = path.startswith("jit(") and not (
            code and code.group(1) in STRUCTURAL)
        stage[name] = innermost(path) if named else None
        body = rest.split(", metadata=", 1)[0]
        for operand in set(_OPERAND.findall(body)):
            users.setdefault(operand, []).append(name)
    out = {}
    for name, own in stage.items():
        seen, todo = {name}, list(users.get(name, ()))
        while own is None and todo:
            user = todo.pop(0)
            if user in seen:
                continue
            seen.add(user)
            found = stage.get(user)
            if found is None:
                todo.extend(users.get(user, ()))
            elif found != UNSCOPED:
                own = found
        out[name] = own or UNSCOPED
    return out


def module_name(event_name: str) -> str:
    """``jit_run_pipeline(1437...)`` → ``jit_run_pipeline``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.133 = s32[...] fusion(...)`` → ``fusion.133``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def events(profile_dir: Path) -> dict:
    """``{"device": {plane: [(op, start_ns, end_ns), ...]}, "modules":
    {plane: [(module, start_ns, end_ns), ...]}, "host": [(name, start_ns,
    end_ns), ...]}`` from the newest trace under ``profile_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (module_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name == devtrace.WINDOW)
    return {"device": device, "modules": modules, "host": host}


def reduce(ev: dict, scopes: dict[str, dict[str, str]]) -> dict | None:
    """Within the traced window: ``stages``, the device seconds of leaf
    ops by module and innermost stage (``scopes[module][op]``, ``unscoped``
    where it names none), and ``runs``, the module runs that started in the
    window, by module; averaged over the device planes. None where the
    trace has no window or no device op inside it."""
    windows = [(s, e) for name, s, e in ev["host"] if name == devtrace.WINDOW]
    if not windows or not ev["device"]:
        return None
    lo, hi = windows[-1]
    stages: dict[str, dict[str, float]] = {}
    runs: dict[str, int] = {}
    planes = 0
    for plane, ops in sorted(ev["device"].items()):
        mods = sorted(ev["modules"].get(plane, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        seen = False
        for op, s, e in devtrace.leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            seen = True
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][0] if i >= 0 and s < mods[i][2] else "none"
            stage = scopes.get(module, {}).get(op, UNSCOPED)
            per = stages.setdefault(module, {})
            per[stage] = per.get(stage, 0.0) + d / 1e9
        for module, s, _ in mods:
            if lo <= s < hi:
                runs[module] = runs.get(module, 0) + 1
        planes += seen
    if not planes:
        return None
    return {"stages": {m: {k: v / planes for k, v in per.items()}
                       for m, per in stages.items()},
            "runs": {m: n / planes for m, n in runs.items()}}


def compiled_scopes(run) -> dict[str, dict[str, str]]:
    """``op_scopes`` of the traced segment's ``run_pipeline`` and of the
    commit gate, from the executables the run used (the same arguments
    find them in the jit cache, so nothing compiles again).

    JAX's persistent compilation cache keys a program without its
    metadata, so an executable cached from the same program compiled
    without scopes names none: the commit gate's program is the same
    with or without its scope, and a gate cached that way reads as one
    unscoped module (see ``gate_ms_per_read``)."""
    prog = run.program
    rows = run.traffic.chunk(run.segment_key, 0)
    low = prog._run.lower(prog.cfg, run.state, *rows, prog.route)
    gate = prog._committed.lower(prog.cfgs[0], run.state)
    return {PIPELINE: op_scopes(low.compile().as_text()),
            GATE: op_scopes(gate.compile().as_text())}


def read(run) -> dict | None:
    """The trace's ``reduce`` with the run's compiled scopes, kept on
    ``run`` for the other readers; None without a trace or where
    ``run_pipeline`` names no stage."""
    if "stage_trace" not in run.__dict__:
        got = None
        if run.trace is not None:
            scopes = compiled_scopes(run)
            if set(scopes[PIPELINE].values()) - {UNSCOPED}:
                got = reduce(events(TRACE_DIR), scopes)
        run.stage_trace = got
    return run.stage_trace


def stage_ms_per_tick(run, stage: str) -> float | None:
    """Device ms of ``stage`` in ``run_pipeline`` per arrival tick of the
    traced segment."""
    got = read(run)
    if got is None or PIPELINE not in got["stages"]:
        return None
    return got["stages"][PIPELINE].get(stage, 0.0) * 1e3 / run.next_tick


def gate_ms_per_read(run) -> float | None:
    """Device ms of the commit gate per run of its program in the traced
    segment: the ops under ``ht.commit_gate``, or the whole module where
    the executable names no scope (one cached without its metadata)."""
    got = read(run)
    if got is None or not got["runs"].get(GATE):
        return None
    gate = got["stages"].get(GATE, {})
    seconds = gate[COMMIT_GATE] if COMMIT_GATE in gate \
        else sum(gate.values())
    return seconds * 1e3 / got["runs"][GATE]
