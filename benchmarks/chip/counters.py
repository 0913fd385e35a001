"""Counts at the stage boundaries of the traced segment.

``run_pipeline`` and ``pipeline_tick`` return per-tick counts of what
crossed each stage boundary (``repro.pipeline.COUNTERS``: ``requests``
batched, batches ``flushed`` and ``admitted``, ids ``ordered``, ``stable``,
``decided``). The window keeps ``admitted`` alone, so
``read(run)`` replays the traced segment after the trace: a fresh state,
the segment's rows, the same compiled programs (chunks of arrival ticks,
then ticks without arrivals until every admitted batch is committed, read
after the last arrival tick and after each drain tick, as the window
drains). The program is deterministic, so these are the traced segment's
counts.

Returns None where the program returns no such counts.
"""
from __future__ import annotations

import sys

import numpy as np


def profile_name(run) -> str:
    """Name of the delay profile the traced segment ran under."""
    prog = run.program
    k = next(i for i, c in enumerate(prog.cfgs) if c is prog.cfg)
    return run.dep["delay_profiles"][k]["name"]


def replay(run) -> dict | None:
    """``{"counts": {key: int64[ticks]}, "arrival_ticks", "reads",
    "profile"}`` of the traced segment, or None without counts."""
    import jax
    prog, traffic = run.program, run.traffic
    state = prog._init(prog.cfgs[0])
    outs = []
    for t in range(0, run.next_tick, traffic.chunk_ticks):
        rows = traffic.chunk(run.segment_key, t)
        state, out = prog._run(prog.cfg, state, *rows, prog.route)
        if "requests" not in out:
            return None
        outs.append(out)
    n = int(prog.admitted(state))
    _, _, com = prog.committed(state)
    reads = 1
    for _ in range(run.dep["drain_ticks_max"]):
        if int(com) >= n:
            break
        state, out = prog._tick(prog.cfg, state, *prog.no_arrivals,
                                prog.route)
        outs.append({k: v[None] for k, v in out.items() if k in outs[0]})
        _, _, com = prog.committed(state)
        reads += 1
    outs = jax.device_get(outs)
    counts = {k: np.concatenate([np.asarray(o[k], np.int64).reshape(-1)
                                 for o in outs]) for k in outs[0]}
    return {"counts": counts, "arrival_ticks": run.next_tick,
            "reads": reads, "profile": profile_name(run)}


def read(run) -> dict | None:
    """``replay(run)``, made once and kept on ``run`` for the other
    readers."""
    if "boundary_counts" not in run.__dict__:
        got = replay(run)
        if got is not None:
            print(f"counters: traced segment under profile "
                  f"{got['profile']}: {got['arrival_ticks']} arrival ticks, "
                  f"{len(got['counts']['admitted']) - got['arrival_ticks']} "
                  f"drain ticks, {got['reads']} commit-gate reads",
                  file=sys.stderr)
        run.boundary_counts = got
    return run.boundary_counts


def mean_wait(run, done: str) -> float | None:
    """Mean ticks from a batch's admission to its ``done`` count
    (``ordered``, ``stable`` or ``decided``): Σₜ(cumulative admitted −
    cumulative done) / Σ admitted, exact when the segment starts empty
    and its drain decides every admitted batch."""
    got = read(run)
    if got is None:
        return None
    c = got["counts"]
    admitted = np.cumsum(c["admitted"])
    return float((admitted - np.cumsum(c[done])).sum() / admitted[-1])
