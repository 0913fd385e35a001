"""Reduction of a profiler trace to the benchmark's device readings.

``events(profile_dir)`` reads the ``.xplane.pb`` the JAX profiler wrote and
keeps two lists: the device's operations (each TPU plane's ``XLA Ops``
line) and the host's annotations (``jax.profiler.TraceAnnotation`` names
that start with ``bench.``). ``reduce`` works on those lists alone, so a
test can feed it a small recorded sample.

Within the traced window (the host annotation ``bench.traced``):

* ``busy_s``: the union of the device operations' intervals, averaged over
  the device planes;
* ``idle_pct``: 100 × (1 − busy / window);
* ``device_ops``: the operations that took most device time, summed by name
  (leaf operations only: a ``while`` or ``conditional`` whose interval holds
  other operations is left out, its body's operations are counted);
* ``idle_gaps``: device idle time, summed by the innermost host annotation
  that was open at each gap's midpoint (``host`` where none was).

``peaks`` reads the chip's published peaks for the readings that need
them; a traced run on a device it does not name fails.
"""
from __future__ import annotations

import bisect
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
WINDOW = "bench.traced"
OPS_LINE = "XLA Ops"
TOP = 10


def peaks(device_kind: str) -> dict:
    """The chip's published peaks (``peaks.json``); a device the table
    does not name is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: "
                       f"{sorted(k for k in table if k != 'source')}")
    return table[device_kind]


def events(profile_dir: Path) -> dict:
    """``{"device": {plane: [(name, start_ns, end_ns), ...]},
    "host": [(name, start_ns, end_ns), ...]}`` from the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"device": device, "host": host}


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(evs) -> list:
    """The events that contain no other event of the same line."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    container = set()
    stack: list[int] = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            container.add(stack[-1])
        stack.append(i)
    return [ev for i, ev in enumerate(evs) if i not in container]


def _host_at(host: list, starts: list, t: float) -> str:
    """The innermost (latest started) annotation open at ``t``; ``host``
    is sorted by start and ``starts`` are its starts."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, e = host[i]
        if t < e and name != WINDOW:
            return name
    return "host"


def reduce(ev: dict) -> dict | None:
    """Device readings of the traced window, or None where the trace has
    no window or no device operation inside it."""
    windows = [(s, e) for name, s, e in ev["host"] if name == WINDOW]
    if not windows or not ev["device"]:
        return None
    lo, hi = windows[-1]
    host = sorted(ev["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    busy, ops, gaps = [], {}, {}
    for plane, evs in sorted(ev["device"].items()):
        spans = union([(s, e) for _, s, e in evs], lo, hi)
        if not spans:
            continue
        busy.append(sum(e - s for s, e in spans))
        for name, s, e in leaves(evs):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
        edges = [lo] + [x for span in spans for x in span] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = _host_at(host, starts, (a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a)
    if not busy:
        return None
    window_ns = hi - lo
    busy_ns = sum(busy) / len(busy)

    def top(d):
        return [[k, v / 1e9 / len(busy)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
            "device_ops": top(ops), "idle_gaps": top(gaps)}
