"""Plain reference of one log segment of the HT-Paxos replicated log.

Written from the deployment's stated rules, in numpy, and independent of the
program under test (it imports nothing from ``src/``). Given the segment's
traffic and the per-node message delays, it says which batches the
disseminators flush, which ordering group each batch belongs to, in which
tick each batch is ordered and committed, and the committed log in the
round-robin merge order.

The rules, per tick ``t`` (a batch's *age* is ``t`` minus its arrival tick):

* batching: each disseminator lane serves clients ``d, d+m, d+2m, ...`` in
  that order; a request of ``q`` bytes costs ``id_bytes + q`` on the wire; a
  batch (header ``message_overhead_bytes + id_bytes``) is closed before a
  request that would take it past the byte budget, and the open batch is
  flushed at the end of every tick (linger 0). Lane ``d``'s ``k``-th batch is
  ``('d<d>', k)``;
* routing: a batch belongs to group ``crc32(repr(bid)) mod G``, and takes
  the next rank of that group, in flush order (lane by lane, each lane's
  closed batches before its tail);
* window: group ``g`` holds the ranks ``[base, base + window)``; a rank is
  known to a node once its age reaches that node's delay;
* ordering: a rank in the window is orderable once a majority of all ``m``
  disseminators know it; the leader orders orderable ranks in rank order,
  at most ``order_budget`` a tick;
* commit: an ordered rank commits once a majority of its partition's
  ``m / G`` disseminators hold it and a majority of the ``s`` sequencers
  have voted for it;
* recycling: at the end of a tick, when fewer than ``recycle_watermark``
  slots are not committed and at least one is, the window drops its
  committed ranks (``base`` = ranks committed);
* merge: every group appends the ranks it ordered this tick, padded to the
  most any group ordered; the log reads round by round, group 0 first;
  the committed log is its longest prefix whose batches are all committed.

So a batch is ordered at the order quorum's age and committed at the
largest of that, the stability quorum's and the commit quorum's ages: with
one delay profile only the slower of the last two can show in what the log
does, which is why a deployment states profiles in which each is slowest.

``quorum`` overrides any of the three majorities (a control that breaks
one of the deployment's guarantees; a stability quorum of 0 is the gate
switched off); the deployment itself always uses majorities.
"""
from __future__ import annotations

import zlib

import numpy as np


def majorities(dep: dict) -> dict:
    """The quorum sizes the deployment states: a majority of each set."""
    m, g, s = dep["disseminators"], dep["groups"], dep["sequencers"]
    return {"order": m // 2 + 1, "stability": (m // g) // 2 + 1,
            "commit": s // 2 + 1}


def route_of(lane: int, seq: int, groups: int) -> int:
    """Owner group of batch ``('d<lane>', seq)``."""
    return zlib.crc32(repr((f"d{lane}", seq)).encode()) % groups


class Routes:
    """Owner group of every batch, computed as far as asked and kept."""

    def __init__(self, lanes: int, groups: int):
        self.lanes, self.groups = lanes, groups
        self.table = np.zeros((lanes, 0), np.int32)

    def __call__(self, lane: np.ndarray, seq: np.ndarray) -> np.ndarray:
        need = int(seq.max()) + 1 if len(seq) else 0
        have = self.table.shape[1]
        if need > have:
            more = np.array([[route_of(d, s, self.groups)
                              for s in range(have, need)]
                             for d in range(self.lanes)], np.int32)
            self.table = np.concatenate(
                [self.table, more.reshape(self.lanes, need - have)], axis=1)
        return self.table[lane, seq]


NEVER = 1 << 40     # an age no batch reaches: the quorum cannot form


def first_age(lags: np.ndarray, quorum: int) -> int:
    """Smallest age at which ``quorum`` of the nodes have the batch (0 for
    a quorum of none)."""
    if quorum <= 0:
        return 0
    srt = np.sort(np.asarray(lags))
    return int(srt[quorum - 1]) if quorum <= len(srt) else NEVER


def flush(dep: dict, sizes: np.ndarray) -> dict:
    """Batches flushed by every lane over the segment's ticks, in flush
    order: arrays ``tick``, ``lane``, ``seq``, ``bytes`` (wire bytes) and
    ``requests``."""
    D = dep["disseminators"]
    header = dep["message_overhead_bytes"] + dep["id_bytes"]
    budget, id_bytes = dep["batch_budget_bytes"], dep["id_bytes"]
    T, C = sizes.shape
    K = -(-C // D)
    pad = np.zeros((T, K * D), np.int32)
    pad[:, :C] = sizes
    slots = np.ascontiguousarray(pad.reshape(T, K, D).transpose(1, 0, 2))
    used = np.full((T, D), header, np.int32)                 # [K, T, D] in
    count = np.zeros((T, D), np.int32)
    closed = np.zeros((K + 1, T, D), bool)
    nbytes = np.zeros((K + 1, T, D), np.int32)
    nreq = np.zeros((K + 1, T, D), np.int32)
    for k in range(K):
        q = slots[k]
        arrived = q > 0
        cost = id_bytes + q
        close = arrived & (count > 0) & (used + cost > budget)
        closed[k] = close
        np.copyto(nbytes[k], used, where=close)
        np.copyto(nreq[k], count, where=close)
        np.copyto(used, header, where=close)
        np.copyto(count, 0, where=close)
        used += np.where(arrived, cost, 0)
        count += arrived
    closed[K] = count > 0
    nbytes[K] = used
    nreq[K] = count
    # flush order: tick, then lane, then position in the lane's stream
    tick, lane, pos = np.nonzero(closed.transpose(1, 2, 0))
    per_lane = np.bincount(lane, minlength=D)
    by_lane = np.argsort(lane, kind="stable")
    seq = np.empty(len(lane), np.int64)
    seq[by_lane] = np.arange(len(lane)) - np.repeat(
        np.cumsum(per_lane) - per_lane, per_lane)
    return {"tick": tick, "lane": lane, "seq": seq,
            "bytes": nbytes[pos, tick, lane].astype(np.int64),
            "requests": nreq[pos, tick, lane].astype(np.int64)}


def segment(dep: dict, lags: dict, sizes: np.ndarray, routes: Routes,
            quorum: dict | None = None) -> dict:
    """The reference's account of one segment whose arrivals are
    ``sizes`` (int32[T, C], 0 = no arrival), followed by ticks without
    arrivals until every batch is committed or ``drain_ticks_max`` pass.

    Returns flush-order batches with their ``group`` and ``rank``;
    per-lane ``n_flushed``/``flushed_bytes``; per tick ``admitted`` and
    ``committed`` (length ``ticks``, the arrival ticks plus the drain);
    ``ordered`` (batches in the merged log) and ``committed``; the committed
    log ``log_group``/``log_rank``/``log_lane``/``log_seq``; and
    ``all_committed``."""
    G, W = dep["groups"], dep["window"]
    D = dep["disseminators"]
    quorum = {**majorities(dep), **(quorum or {})}
    T = sizes.shape[0]
    b = flush(dep, sizes)
    b["group"] = routes(b["lane"], b["seq"]).astype(np.int64)
    b["rank"] = np.zeros(len(b["tick"]), np.int64)
    for g in range(G):
        sel = b["group"] == g
        b["rank"][sel] = np.arange(int(sel.sum()))
    age_order = first_age(lags["ack"], quorum["order"])
    age_commit = max(first_age(lags["hold"], quorum["stability"]),
                     first_age(lags["vote"], quorum["commit"]))

    at = [b["tick"][b["group"] == g] for g in range(G)]     # by rank
    total = len(b["tick"])
    base = np.zeros(G, np.int64)
    assigned, decided = np.zeros(G, np.int64), np.zeros(G, np.int64)
    assigned_at, decided_at = [], []
    t = 0
    while True:
        for g in range(G):
            known = np.searchsorted(at[g], t - age_order, side="right")
            orderable = min(base[g] + W, known)
            assigned[g] = max(assigned[g], min(assigned[g]
                                               + dep["order_budget"],
                                               orderable))
            voted = np.searchsorted(at[g], t - age_commit, side="right")
            decided[g] = max(decided[g], min(assigned[g], voted))
            live_done = decided[g] - base[g]
            if live_done > 0 and W - live_done < dep["recycle_watermark"]:
                base[g] = decided[g]
        assigned_at.append(assigned.copy())
        decided_at.append(decided.copy())
        t += 1
        if t >= T and (decided.sum() == total
                       or t - T >= dep["drain_ticks_max"]):
            break
    ticks = t
    assigned_at = np.array(assigned_at).T                   # [G, ticks]
    decided_at = np.array(decided_at).T

    # the merged log: entry (g, rank) ordered at tick ta, as the r-th of
    # that tick's entries of group g, sits at (ta, r, g)
    log_g, log_rank, log_key, log_done = [], [], [], []
    for g in range(G):
        n = int(assigned_at[g, -1])
        ranks = np.arange(n)
        ta = np.searchsorted(assigned_at[g], ranks, side="right")
        before = np.concatenate([[0], assigned_at[g]])[ta]
        log_g.append(np.full(n, g))
        log_rank.append(ranks)
        log_key.append(np.stack([ta, ranks - before]))
        log_done.append(np.searchsorted(decided_at[g], ranks, side="right"))
    log_g = np.concatenate(log_g)
    log_rank = np.concatenate(log_rank)
    key = np.concatenate(log_key, axis=1)
    done = np.concatenate(log_done)
    order = np.lexsort((log_g, key[1], key[0]))
    log_g, log_rank, done = log_g[order], log_rank[order], done[order]
    done_by = np.maximum.accumulate(done) if len(done) else done
    committed = np.searchsorted(done_by, np.arange(ticks), side="right")

    idx = np.empty(len(log_g), np.int64)       # flush-order batch index
    for g in range(G):
        sel = log_g == g
        idx[sel] = np.flatnonzero(b["group"] == g)[log_rank[sel]]
    n_log = int(committed[-1]) if ticks else 0
    idx = idx[:n_log]
    return {
        **b,
        "admitted": np.bincount(b["tick"], minlength=ticks)[:ticks],
        "n_flushed": np.bincount(b["lane"], minlength=D),
        "flushed_bytes": np.bincount(b["lane"], weights=b["bytes"],
                                     minlength=D).astype(np.int64),
        "ordered": assigned_at.sum(axis=0),
        "committed": committed,
        "ticks": ticks,
        "log_group": log_g[:n_log], "log_rank": log_rank[:n_log],
        "log_lane": b["lane"][idx], "log_seq": b["seq"][idx],
        "all_committed": bool(n_log == total),
        "n_batches": total,
    }
