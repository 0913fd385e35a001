"""The comparison that decides ``correct``.

Each segment the window drove is compared with the plain reference
(``reference.segment``) on the same rows. Every number is a count of
disagreements, summed over the segments, and its limit is 0: the
comparison is exact.

* ``admission``: ranks whose batch id or arrival tick differs, plus the
  difference in batches admitted, per group;
* ``wire``: lanes whose batch count or wire bytes differ;
* ``admitted_ticks``: ticks whose count of admitted batches differs;
* ``order``: positions of the committed log whose (group, rank) or batch id
  differs, plus the difference in length;
* ``ordered_reads``: counts of batches in the merged log read by the host
  that differ from the reference's count at that tick (the order quorum
  decides it);
* ``commit_reads``: committed counts read by the host that differ from the
  reference's count at that tick (the stability and commit quorums decide
  it);
* ``uncommitted``: admitted batches not committed when the drain ended;
* ``duplicates``: batches committed more than once;
* ``overflow``: segments whose admission record or merge log overflowed.
"""
from __future__ import annotations

import numpy as np

CHECKS = ("admission", "wire", "admitted_ticks", "order", "ordered_reads",
          "commit_reads", "uncommitted", "duplicates", "overflow")
LIMITS = {name: 0 for name in CHECKS}


def _differ(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    return int(np.count_nonzero(a[:n] != b[:n])) + abs(len(a) - len(b))


def segment(dep: dict, got: dict, ref: dict) -> dict:
    """Disagreements of one segment. ``got`` is the program's record on
    the host: the ``Program.record`` arrays plus ``reads`` [(tick,
    ordered, committed)] and ``admitted`` (int per tick)."""
    G, S = dep["groups"], dep["seq_capacity"]
    stride = dep["admission_capacity"]
    out = dict.fromkeys(CHECKS, 0)

    counts = np.asarray(got["admit_count"])
    for g in range(G):
        sel = ref["group"] == g
        want_code = ref["lane"][sel] * S + ref["seq"][sel]
        n = int(min(counts[g], stride))
        out["admission"] += (
            _differ(np.asarray(got["bid_code"][g, :n]), want_code)
            + _differ(np.asarray(got["admit_tick"][g, :n]), ref["tick"][sel]))
    out["wire"] = int(np.count_nonzero(
        (np.asarray(got["n_flushed"]) != ref["n_flushed"])
        | (np.asarray(got["flushed_bytes"]) != ref["flushed_bytes"])))
    out["admitted_ticks"] = _differ(np.asarray(got["admitted"]),
                                    ref["admitted"])

    com = int(got["committed"])
    ids = np.asarray(got["merged"])[:max(com, 0)].astype(np.int64)
    g_of, k_of = ids // stride, ids % stride
    ok = (g_of >= 0) & (g_of < G) & (k_of < got["bid_code"].shape[1])
    code = np.full(len(ids), -1, np.int64)
    code[ok] = np.asarray(got["bid_code"])[g_of[ok], k_of[ok]]
    want = ref["log_lane"] * S + ref["log_seq"]
    out["order"] = max(_differ(g_of, ref["log_group"]),
                       _differ(k_of, ref["log_rank"]),
                       _differ(code, want))

    last = len(ref["committed"]) - 1
    out["ordered_reads"] = sum(
        int(o != ref["ordered"][min(t, last)]) for t, o, _ in got["reads"])
    out["commit_reads"] = sum(
        int(c != ref["committed"][min(t, last)]) for t, _, c in got["reads"])
    out["uncommitted"] = max(int(counts.sum()) - com,
                             ref["n_batches"] - com, 0)
    out["duplicates"] = len(ids) - len(np.unique(ids))
    out["overflow"] = int(bool(got["overflowed"])
                          or int(got["merge_overflowed"]) > 0)
    return out


def total(per_segment: list[dict]) -> dict:
    """Sum of the segments' disagreements, each beside its limit."""
    return {name: {"value": sum(s[name] for s in per_segment),
                   "limit": LIMITS[name]} for name in CHECKS}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
