"""Render every committed ``benchmarks/BENCH_*.json`` into one markdown
table at ``docs/BENCHMARKS.md`` (name, key ratio, bar, pass/fail).

The table is *generated* — edit the benches, not the markdown:

    PYTHONPATH=src python benchmarks/run.py          # refresh the JSONs
    python benchmarks/summarize.py                   # rewrite the table
    python benchmarks/summarize.py --check           # CI drift gate

``--check`` re-renders in memory and exits 1 if docs/BENCHMARKS.md does
not match, so a PR that changes a bench's JSON without regenerating the
table (or vice versa) fails CI. Rendering is a pure function of the
JSON files — no timestamps, no environment — which is what makes the
drift check meaningful.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
DOC = HERE.parent / "docs" / "BENCHMARKS.md"

# per-bench key-ratio spec: JSON field holding the headline ratio, a
# short meaning, and the field (or callable) deciding pass/fail. A
# bench absent here still renders (ratio/pass show "—"), so adding a
# new BENCH_*.json never breaks the table — it just nudges you to give
# it a spec.
SPEC = {
    "adaptive_batching": {
        "ratio": "speedup_vs_lockstep",
        "meaning": "adaptive vs lock-step ids/s (bit-identical merge)",
        "ok": lambda r: r["target_met"] and r["bit_identical"],
        "target": lambda r: f">={r['target']:g}x",
    },
    "membership": {
        "ratio": "post_flip_vs_static",
        "meaning": "post-reconfig ids/s vs always-static fleet",
        "ok": lambda r: r["meets_bar"],
        "target": lambda r: ">=0.90x",
    },
    "multidevice": {
        "ratio": "speedup",
        "meaning": "meshed merged ids/s, every visible device vs one "
                   "(sha256 bit-identity asserted first)",
        "ok": lambda r: r["bit_identical"] and r["meets_bar"],
        "target": lambda r: f">=2.0x ({r['devices']} devices)",
    },
    "pipeline": {
        "ratio": "end_to_end_vs_isolated",
        "meaning": "closed pipeline vs stage-isolated engine ids/s",
        "ok": lambda r: r["meets_bar"],
        "target": lambda r: ">=0.85x",
    },
    "sharded_dissemination": {
        "ratio": "in_reduction_vs_global",
        "meaning": "per-node replication bytes, global / partitioned",
        "ok": lambda r: r["partitioned_below_global"],
        "target": lambda r: f"~{r['groups']}x (G={r['groups']})",
    },
    "sharded_engine": {
        "ratio": "speedup_vs_G1",
        "meaning": "merged ids/s vs G=1 at equal total window",
        "ok": lambda r: r["speedup_vs_G1"] >= 0.9 or r["G"] == 1,
        "target": lambda r: f"~{r['G']}x (G={r['G']})",
    },
    "window_recycling": {
        "ratio": "sustained_ratio",
        "meaning": "mean later-generation ids/s vs first generation",
        "ok": lambda r: r["sustained_ratio"] >= 0.90,
        "target": lambda r: ">=0.90x",
    },
}

BAR_UNIT = 0.25          # one block per 0.25x
BAR_MAX = 32


def _bar(ratio: float) -> str:
    n = max(1, min(BAR_MAX, round(ratio / BAR_UNIT)))
    return "█" * n


def render() -> str:
    lines = [
        "# Benchmark results",
        "",
        "<!-- GENERATED FILE — do not edit. Rebuild with: -->",
        "<!--   PYTHONPATH=src python benchmarks/run.py  -->",
        "<!--   python benchmarks/summarize.py           -->",
        "",
        "Rendered from the committed `benchmarks/BENCH_*.json` by",
        "`benchmarks/summarize.py` (CI fails on drift via `--check`).",
        f"One bar block = {BAR_UNIT:g}x. Timings are CPU and noisy;",
        "the ratios are the acceptance quantities.",
        "",
        "| bench / row | key ratio | target | | pass |",
        "| --- | ---: | --- | :--- | :---: |",
    ]
    for path in sorted(HERE.glob("BENCH_*.json")):
        stem = path.name.removeprefix("BENCH_").removesuffix(".json")
        spec = SPEC.get(stem)
        rows = json.loads(path.read_text())
        for row in rows:
            name = row.get("name", stem)
            # a spec-less bench, or a context row without the bench's
            # key ratio (e.g. multidevice per-device-count timings),
            # still renders — just without a ratio/pass verdict
            if spec is None or spec["ratio"] not in row:
                lines.append(f"| `{name}` | — | — |  | — |")
                continue
            ratio = float(row[spec["ratio"]])
            ok = bool(spec["ok"](row))
            lines.append(
                f"| `{name}` | {ratio:.2f}x | {spec['target'](row)} "
                f"| {_bar(ratio)} | {'✅' if ok else '❌'} |")
    lines += [""]
    for stem, spec in sorted(SPEC.items()):
        lines.append(f"- **{stem}** — {spec['meaning']}.")
    lines += [""]
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--check", action="store_true",
                   help="exit 1 if docs/BENCHMARKS.md is out of date "
                        "instead of rewriting it")
    args = p.parse_args(argv)
    text = render()
    if args.check:
        current = DOC.read_text() if DOC.exists() else ""
        if current != text:
            sys.stderr.write(
                "docs/BENCHMARKS.md is out of date with the committed "
                "BENCH_*.json files.\nRegenerate it:\n"
                "    python benchmarks/summarize.py\n")
            return 1
        print("docs/BENCHMARKS.md is up to date")
        return 0
    DOC.write_text(text)
    print(f"wrote {DOC} ({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
