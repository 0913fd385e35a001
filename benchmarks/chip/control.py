"""The controls of the comparison: each breaks one guarantee the deployment
states, and ``compare`` has to find each not correct.

* ``order_quorum_1``, ``stability_quorum_1``, ``commit_quorum_1``: the plain
  reference put in the program's place with one quorum cut to one node (a
  batch ordered on one disseminator's id, stable on one holder, committed on
  one sequencer's vote);
* ``ungated``: the reference with the stability gate switched off;
* ``ungated_program`` (``--program-seconds``): the program's own ungated
  path (every id seeded stable) driven through a whole run of the cell.

    python3 benchmarks/chip/control.py --workload paper_dc_g4.saturated \\
        --segments 4 --seeds 11 12 13 [--program-seconds 10]

draws each seed's segments at the cell's own size, as a run of the cell
would, and prints, one JSON line per seed, the summed disagreements of each
control, and of the sound reference against itself, beside each limit.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import compare
import reference
from system import Program

CONTROLS = {"order_quorum_1": {"order": 1},
            "stability_quorum_1": {"stability": 1},
            "commit_quorum_1": {"commit": 1},
            "ungated": {"stability": 0}}


class Ungated(Program):
    """The program with its stability gate switched off."""

    gate_open = True


def as_record(dep: dict, acct: dict) -> dict:
    """A reference account in the form ``compare.segment`` reads from the
    program: admission and wire records, the committed log as ids, and the
    merged and committed counts read from the last arrival tick on."""
    G, R = dep["groups"], dep["admission_capacity"]
    S = dep["seq_capacity"]
    code = np.full((G, R), -1, np.int64)
    tick = np.zeros((G, R), np.int64)
    count = np.zeros(G, np.int64)
    for g in range(G):
        sel = acct["group"] == g
        n = int(sel.sum())
        count[g] = n
        code[g, :n] = acct["lane"][sel] * S + acct["seq"][sel]
        tick[g, :n] = acct["tick"][sel]
    ids = acct["log_group"] * R + acct["log_rank"]
    arrival_ticks = int(acct["tick"].max()) + 1 if len(acct["tick"]) else 0
    first = max(arrival_ticks - 1, 0)
    return {"merged": ids, "committed": len(ids), "admit_count": count,
            "admit_tick": tick, "bid_code": code,
            "n_flushed": acct["n_flushed"],
            "flushed_bytes": acct["flushed_bytes"],
            "overflowed": False, "merge_overflowed": 0,
            "admitted": acct["admitted"],
            "reads": [(t, int(acct["ordered"][t]), int(acct["committed"][t]))
                      for t in range(first, acct["ticks"])]}


def readings(dep: dict, profiles: list[dict], segments: list,
             routes: reference.Routes) -> dict:
    """Summed disagreements of each control and of the sound reference
    with itself over ``segments`` (segment k: int32[T, C] sizes, run
    under delay profile k mod the number of profiles)."""
    per = {name: [] for name in ("sound", *CONTROLS)}
    for k, sizes in enumerate(segments):
        lags = profiles[k % len(profiles)]
        ref = reference.segment(dep, lags, sizes, routes)
        per["sound"].append(compare.segment(dep, as_record(dep, ref), ref))
        for name, quorum in CONTROLS.items():
            bad = reference.segment(dep, lags, sizes, routes, quorum=quorum)
            per[name].append(compare.segment(dep, as_record(dep, bad), ref))
    return {name: compare.total(v) for name, v in per.items()}


def main(argv=None) -> None:
    import harness
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--segments", type=int, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--program-seconds", type=float, default=0.0,
                   help="also run the program's ungated path for a window "
                        "this long (on the chip)")
    args = p.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    from generator import Traffic, seed_key
    from system import node_lags
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    _, dep, mix = harness.load_cell(bench, args.workload)
    traffic = Traffic(mix, dep["clients"])
    routes = reference.Routes(dep["disseminators"], dep["groups"])
    if args.program_seconds:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache(harness.ROOT)
    for seed in args.seeds:
        key = seed_key(seed)
        segs = [traffic.segment_sizes(traffic.segment_key(key, s),
                                      dep["segment_ticks"])
                for s in range(args.segments)]
        r = readings(dep, node_lags(dep), segs, routes)
        line = {"workload": args.workload, "seed": seed,
                "correct": {name: compare.passed(c) for name, c in r.items()},
                **r}
        if args.program_seconds:
            run = harness.run_cell(bench, args.workload, seed,
                                   args.program_seconds, False,
                                   program=Ungated)
            line["correct"]["ungated_program"] = run["correct"]
            line["ungated_program"] = run["checks"]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
