"""On-chip benchmark of the HT-Paxos replicated log: one cell, one run.

    python3 benchmarks/chip/harness.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a deployment (``configs/<config>.json``)
and a traffic mix (``traffic/<traffic>.json``); per-layer metrics are read
by ``metrics/<name>.py`` and end-to-end metrics by ``end_to_end/<name>.py``.
Adding a cell, a mix or a metric adds files; this one does not change.
A deployment's ``mesh_devices`` (the group-sharded engine, ``system.py``)
is at most its cell's ``chips``: the run then fails on a host with fewer.

A run builds the deployment, warms up every shape the cell uses (set-up),
then drives the program for ``--seconds``: chunks of ticks through
``run_pipeline``, back to back, until the time is up.

The log rotates every ``segment_ticks`` arrival ticks: ticks without arrivals
run until every admitted batch is committed, the segment's record is kept
on the device, and the next segment starts from ``init_pipeline`` under the
deployment's next delay profile. Drain and re-init are inside the window.
After the window, each segment is compared with the plain reference
(``reference.py``, ``compare.py``).

With ``--trace 1`` a phase of ``trace_ticks`` arrival ticks after the window
runs under the profiler (device busy and idle time, ``devtrace.py``) and the
per-layer metrics are read; otherwise the end-to-end metrics are printed. The last stdout line is
one JSON object; the compared numbers and their limits close stderr.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
CACHE = ROOT / ".bench_cache"
TRACE_DIR = ROOT / ".bench_trace"


def fail(msg: str):
    raise SystemExit(f"harness: {msg}")


def now() -> float:
    return time.perf_counter()


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    """(cell, deployment, traffic mix) of workload ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    dep = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    return cell, dep, mix


def cell_metrics(bench: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics this cell reports."""
    def here(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


class Window:
    """Drives the program through log segments and keeps what it made."""

    def __init__(self, prog, traffic, dep: dict, key):
        import jax
        self.jax = jax
        self.prog, self.traffic, self.dep, self.key = prog, traffic, dep, key
        self.segments: list[dict] = []
        self.steps: list[float] = []     # seconds per chunk

    def _annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(f"bench.{name}")

    def _read(self, state, seg: dict, t: int):
        """Reads the merged and committed counts after tick ``t``."""
        merged, count, com = self.prog.committed(state)
        seg["reads"].append((t, int(count), int(com)))
        return merged, com

    def _drain(self, state, seg: dict):
        """Ticks without arrivals until every admitted batch is committed
        (at most ``drain_ticks_max``); reads the counts after each."""
        prog = self.prog
        t = seg["ticks"] - 1
        with self._annotate("read"):
            merged, com = self._read(state, seg, t)
            n = int(prog.admitted(state))
        for _ in range(self.dep["drain_ticks_max"]):
            if seg["reads"][-1][2] >= n:
                break
            with self._annotate("drain"):
                state, adm = prog.tick(state, *prog.no_arrivals)
                t += 1
                merged, com = self._read(state, seg, t)
            seg["admitted"].append(adm)
        seg["record"] = prog.record(state, merged, com)
        return state

    def _new_segment(self):
        index = len(self.segments)
        seg = {"index": index, "ticks": 0, "admitted": [], "reads": []}
        self.segments.append(seg)
        with self._annotate("rotate"):
            state = self.prog.init(index)
        return seg, state, self.traffic.segment_key(self.key, index)

    def drive(self, seconds: float, max_ticks: int | None = None,
              keep=None) -> tuple[float, float]:
        """Chunks of ticks until ``seconds`` have passed (or ``max_ticks``
        arrival ticks ran), rotating the log; returns (start, end).
        ``keep(state)`` sees the state where each segment's arrivals end."""
        chunk = self.traffic.chunk_ticks
        seg_ticks = self.dep["segment_ticks"]
        t0 = now()
        deadline = t0 + seconds
        done = 0
        while True:
            seg, state, skey = self._new_segment()
            pending = synced = None
            while seg["ticks"] < seg_ticks:
                with self._annotate("dispatch"):
                    a, s = self.traffic.chunk(skey, seg["ticks"])
                    state, adm = self.prog.run_chunk(state, a, s)
                seg["admitted"].append(adm)
                if pending is not None:
                    with self._annotate("sync"):
                        pending.block_until_ready()
                    if synced is not None:
                        self.steps.append(now() - synced)
                    synced = now()
                pending = adm
                seg["ticks"] += chunk
                done += chunk
                if now() >= deadline or (max_ticks and done >= max_ticks):
                    break
            if keep is not None:
                keep(state)
            state = self._drain(state, seg)
            del state
            if now() >= deadline or (max_ticks and done >= max_ticks):
                return t0, now()


class Run:
    """What a metric reader may look at (see ``metrics/`` and
    ``end_to_end/``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def copy_tree(tree):
    """A device copy of every array of ``tree``, synced."""
    import jax
    return jax.block_until_ready(jax.tree.map(lambda x: x.copy(), tree))


def warm_up(window: Window) -> None:
    """Run every program and traffic shape the cell uses once: two chunks,
    a drain tick and the reads, under each delay profile. The second chunk
    takes a state as a chunk leaves it: on a device mesh that state is
    placed otherwise than a fresh one, and ``run_pipeline`` compiles for
    each placement."""
    import jax
    prog, traffic = window.prog, window.traffic
    skey = traffic.segment_key(window.key, 0)
    for k in range(len(prog.cfgs)):
        state = prog.init(k)
        for t in (0, traffic.chunk_ticks):
            state, _ = prog.run_chunk(state, *traffic.chunk(skey, t))
        state, _ = prog.tick(state, *prog.no_arrivals)
        jax.block_until_ready((prog.committed(state), prog.admitted(state),
                               prog.record(state,
                                           *prog.committed(state)[::2])))


def fetch(seg: dict) -> dict:
    import jax
    import numpy as np
    got = jax.device_get(seg["record"])
    got["admitted"] = np.concatenate(
        [np.asarray(a).reshape(-1) for a in jax.device_get(seg["admitted"])])
    got["reads"] = seg["reads"]
    return got


def committed_requests(dep: dict, got: dict, ref: dict) -> int:
    """Requests in the batches of the program's committed log, counted
    from the reference's batches (by group and rank)."""
    import numpy as np
    G, stride = dep["groups"], dep["admission_capacity"]
    per = [ref["requests"][ref["group"] == g] for g in range(G)]
    table = np.zeros((G, max(len(p) for p in per) + 1), np.int64)
    for g, p in enumerate(per):
        table[g, :len(p)] = p
    ids = np.asarray(got["merged"])[:max(int(got["committed"]), 0)]
    g, k = ids // stride, ids % stride
    ok = (g >= 0) & (g < G) & (k < table.shape[1])
    return int(table[g[ok], k[ok]].sum())


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, *, program=None, require_tpu: bool = True,
             t_process: float | None = None,
             t_devices: float | None = None) -> dict:
    """One run of cell ``name``; returns the result object.

    ``program`` replaces the ``Program`` class (a test breaks the timed
    path this way); ``require_tpu=False`` lets a test run on the CPU.
    ``t_process`` / ``t_devices``: when the process started and when JAX
    had found its devices (for the set-up's split on stderr)."""
    import jax
    import numpy as np
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    import compare
    import devtrace
    import reference
    from generator import Traffic, seed_key
    from system import Program, node_lags

    t_process = T_PROCESS if t_process is None else t_process
    t_devices = t_process if t_devices is None else t_devices
    devices = jax.devices()
    cell, dep, mix = load_cell(bench, name)
    if require_tpu:
        if devices[0].platform != "tpu":
            fail(f"needs a TPU, JAX found platform {devices[0].platform!r}")
        if len(devices) < cell["chips"]:
            fail(f"cell {name} needs {cell['chips']} chips, JAX found "
                 f"{len(devices)}")
    e2e_specs, layer_specs = cell_metrics(bench, name)

    profiles = node_lags(dep)
    t_build = now()
    prog = (program or Program)(dep, profiles, CACHE)
    traffic = Traffic(mix, dep["clients"])
    window = Window(prog, traffic, dep, seed_key(seed))
    t_warm = now()
    warm_up(window)
    # what set-up made stays alive for the whole run: keep it out of the
    # collector's scans, so a collection in the window walks only new objects
    gc.collect()
    gc.freeze()
    setup_s = now() - t_process
    print(f"setup: imports and device start {t_build - t_process:.3f} s "
          f"(jax and the device alone {t_devices - t_process:.3f} s), build "
          f"and route table {t_warm - t_build:.3f} s, warm-up (compile or "
          f"cache load) {now() - t_warm:.3f} s", file=sys.stderr)

    t0, t_end = window.drive(seconds)
    n_window = len(window.segments)
    steps = np.asarray(window.steps) * 1e3
    if len(steps):
        slow = np.argsort(steps)[::-1][:5]
        print(f"window: {t_end - t0:.3f} s, {n_window} segment(s), "
              f"{len(steps)} chunks of median {np.median(steps):.3f} ms; "
              f"slowest (chunk: ms) "
              + ", ".join(f"{i}: {steps[i]:.1f}" for i in slow),
              file=sys.stderr)

    reduced, layer = None, {}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # the state copy kept in the traced phase compiles its copy
        # programs here, so the trace holds no host time for them: on a
        # state of the traced segment's profile, placed as a chunk leaves it
        rows = traffic.chunk(traffic.segment_key(window.key, 0), 0)
        state, _ = prog.run_chunk(prog.init(len(window.segments)), *rows)
        copy_tree(state)
        del state
        kept = {}
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                window.drive(math.inf, max_ticks=mix["trace_ticks"],
                             keep=lambda st: kept.setdefault(
                                 "state", copy_tree(st)))
                jax.block_until_ready(window.segments[-1]["record"])
        finally:
            jax.profiler.stop_trace()
        reduced = devtrace.reduce(devtrace.events(TRACE_DIR))
        chip = devtrace.peaks(devices[0].device_kind) if require_tpu else None
        ctx = Run(program=prog, traffic=traffic, dep=dep, trace=reduced,
                  peaks=chip, state=kept["state"],
                  segment_key=traffic.segment_key(window.key,
                                                  len(window.segments) - 1),
                  next_tick=window.segments[-1]["ticks"])
        for spec in layer_specs:
            value = load_module(HERE / "metrics" / f"{spec['name']}.py") \
                .read(ctx)
            if value is not None:
                layer[spec["name"]] = {"value": float(value),
                                       "unit": spec["unit"]}
        del ctx, kept

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    got = [fetch(s) for s in window.segments]
    for s in window.segments:
        s.pop("record")
        s.pop("admitted")
    del prog, window.prog

    routes = reference.Routes(dep["disseminators"], dep["groups"])
    per_seg, requests, offered = [], 0, 0
    for seg, g in zip(window.segments, got):
        sizes = traffic.segment_sizes(
            traffic.segment_key(window.key, seg["index"]), seg["ticks"])
        lags = profiles[seg["index"] % len(profiles)]
        ref = reference.segment(dep, lags, sizes, routes)
        per_seg.append(compare.segment(dep, g, ref))
        if seg["index"] < n_window:
            offered += int(ref["requests"].sum())
            requests += committed_requests(dep, g, ref)
    checks = compare.total(per_seg)

    ctx = Run(setup_s=setup_s, window_s=t_end - t0,
              committed_requests=requests)
    metrics = layer
    if not trace:
        metrics = {}
        for spec in e2e_specs:
            value = load_module(HERE / "end_to_end" / f"{spec['name']}.py") \
                .read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": compare.passed(checks), "attempted": offered,
              "failed": offered - requests, "metrics": metrics,
              "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import use_compile_cache
    platform = jax.devices()[0].platform
    t_devices = now()
    if platform != "tpu":
        fail(f"needs a TPU, JAX found platform {platform!r}")
    use_compile_cache(ROOT)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_devices=t_devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
