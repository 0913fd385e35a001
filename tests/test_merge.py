"""Merge-stage properties (repro.engine.merge): the merged log is a legal
interleaving preserving each group's internal order, agrees with the
pure-Python oracle, and is invariant under tick batching (the same entry
streams appended in different chunkings yield the same merged prefix)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.invariants import check_legal_interleaving
from repro.engine import merge as M
from repro.runtime.statemachine import Command, MergedCommandLog


def random_streams(rng, G, max_len=24, skip_p=0.25):
    """Per-group entry streams with explicit SKIP tokens; real entries are
    globally unique ints (id = g*1000 + k)."""
    streams = []
    for g in range(G):
        n = int(rng.integers(0, max_len + 1))
        ks = iter(range(n))
        streams.append([M.SKIP if rng.random() < skip_p
                        else g * 1000 + next(ks) for _ in range(n)])
    return streams


def append_in_chunks(state, streams, chunk_sizes_fn):
    """Append each group's stream to MergeState in per-round chunks; every
    round appends the same count to every group, padding shorter groups
    with SKIP (the engine's per-tick skip-padding discipline)."""
    cursors = [0] * len(streams)
    while any(c < len(s) for c, s in zip(cursors, streams)):
        k = chunk_sizes_fn()
        take = [min(k, len(s) - c) for c, s in zip(cursors, streams)]
        width = max(take)
        if width == 0:
            break
        entries = np.full((len(streams), width), M.SKIP, np.int32)
        for g, s in enumerate(streams):
            for j in range(take[g]):
                entries[g, j] = s[cursors[g] + j]
            cursors[g] += take[g]
        state = M.append_entries(state, jnp.asarray(entries),
                                 jnp.full((len(streams),), width, jnp.int32))
    # groups whose stream ended early stay at a lower watermark — the merge
    # must still emit the maximal prefix, not stall or overrun
    return state


@pytest.mark.parametrize("seed", range(8))
def test_merged_prefix_agrees_with_oracle(seed):
    rng = np.random.default_rng(seed)
    G = int(rng.integers(1, 6))
    streams = random_streams(rng, G)
    st = M.init_merge(G, 64)
    for g, s in enumerate(streams):       # append whole stream per group
        if s:
            e = np.full((G, len(s)), M.SKIP, np.int32)
            e[g, :] = s
            counts = np.zeros((G,), np.int32)
            counts[g] = len(s)
            st = M.append_entries(st, jnp.asarray(e), jnp.asarray(counts))
    out, n = M.merged_prefix(st)
    got = np.asarray(out)[:int(n)].tolist()
    assert got == M.oracle_merge(streams)
    # prefix is a legal interleaving of the per-group (skip-free) orders
    orders = [[x for x in s if x != M.SKIP] for s in streams]
    assert M.oracle_is_legal_interleaving(got, orders)
    assert not check_legal_interleaving(got, orders)


@pytest.mark.parametrize("seed", range(8))
def test_merge_invariant_under_chunking(seed):
    """Tick-batching invariance: the same per-group entry streams split
    into different append chunkings yield the same merged prefix."""
    rng = np.random.default_rng(100 + seed)
    G = int(rng.integers(2, 5))
    streams = random_streams(rng, G, max_len=20)
    # equalize stream lengths (engine skip-padding guarantees this per run)
    L = max((len(s) for s in streams), default=0)
    streams = [s + [M.SKIP] * (L - len(s)) for s in streams]

    st_one = append_in_chunks(M.init_merge(G, 64), streams, lambda: L or 1)
    rng2 = np.random.default_rng(999 + seed)
    st_many = append_in_chunks(M.init_merge(G, 64), streams,
                               lambda: int(rng2.integers(1, 4)))
    out1, n1 = M.merged_prefix(st_one)
    out2, n2 = M.merged_prefix(st_many)
    assert int(n1) == int(n2)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))


def test_watermark_partial_round():
    """Unequal watermarks: emit full rounds plus the partial round up to
    the first lagging group, never beyond."""
    st = M.init_merge(2, 8)
    st = M.append_entries(st, jnp.asarray([[1, 2, 3], [4, 0, 0]], jnp.int32),
                          jnp.asarray([3, 1], jnp.int32))
    out, n = M.merged_prefix(st)
    # rounds: (1,4) full; round 1 partial: group0 has 2, group1 missing → stop
    assert np.asarray(out)[:int(n)].tolist() == [1, 4, 2]
    # catching group 1 up extends the previous prefix monotonically
    st = M.append_entries(st, jnp.asarray([[0, 0, 0], [5, 6, 0]], jnp.int32),
                          jnp.asarray([0, 2], jnp.int32))
    out2, n2 = M.merged_prefix(st)
    assert np.asarray(out2)[:int(n2)].tolist() == [1, 4, 2, 5, 3, 6]


def test_skips_dropped_but_hold_positions():
    st = M.init_merge(3, 8)
    st = M.append_entries(
        st, jnp.asarray([[7, M.SKIP], [M.SKIP, 8], [M.SKIP, M.SKIP]],
                        jnp.int32), jnp.asarray([2, 2, 2], jnp.int32))
    out, n = M.merged_prefix(st)
    assert np.asarray(out)[:int(n)].tolist() == [7, 8]


def test_entries_from_assigned_orders_and_pads():
    assigned = jnp.asarray([[5, -1, 6], [-1, -1, -1]], jnp.int32)
    slot_ids = jnp.asarray([[10, 11, 12], [20, 21, 22]], jnp.int32)
    entries, counts, dropped = M.entries_from_assigned(assigned, slot_ids, 3)
    assert np.asarray(entries).tolist() == [[10, 12, M.SKIP]] + \
        [[M.SKIP, M.SKIP, M.SKIP]]
    # counts equalized to the per-tick max so the idle group appends skips
    assert np.asarray(counts).tolist() == [2, 2]
    assert int(dropped) == 0


def test_entries_from_assigned_reports_overassignment():
    """Regression: ids truncated by an undersized max_entries used to
    vanish silently — they must be surfaced in the dropped count (and the
    run_* loops debug-assert it stays zero)."""
    assigned = jnp.asarray([[0, 1, 2], [3, -1, -1]], jnp.int32)
    slot_ids = jnp.asarray([[10, 11, 12], [20, 21, 22]], jnp.int32)
    entries, counts, dropped = M.entries_from_assigned(assigned, slot_ids, 2)
    assert int(dropped) == 1                       # group 0 lost one id
    assert np.asarray(counts).tolist() == [2, 2]   # clamped to max_entries
    assert np.asarray(entries).tolist()[0] == [10, 11]
    # widening the buffer back to the assignment count drops nothing
    _, _, d2 = M.entries_from_assigned(assigned, slot_ids, 3)
    assert int(d2) == 0


def test_append_entries_reports_capacity_overflow():
    """Regression: appends past capacity L advanced the watermark but
    wrote no cells — silently corrupting the merged order. They are now
    counted per group in MergeState.overflowed."""
    st = M.init_merge(2, 4)
    e = jnp.asarray([[1, 2, 3], [4, 5, -2]], jnp.int32)
    st = M.append_entries(st, e, jnp.asarray([3, 3], jnp.int32))
    assert np.asarray(st.overflowed).tolist() == [0, 0]
    # group 0 appends 3 more: only 1 cell left → 2 overflow
    st = M.append_entries(st, e, jnp.asarray([3, 0], jnp.int32))
    assert np.asarray(st.overflowed).tolist() == [2, 0]
    assert np.asarray(st.watermarks).tolist() == [6, 3]
    # exactly-at-capacity append overflows nothing
    st2 = M.init_merge(1, 3)
    st2 = M.append_entries(st2, jnp.asarray([[7, 8, 9]], jnp.int32),
                           jnp.asarray([3], jnp.int32))
    assert np.asarray(st2.overflowed).tolist() == [0]
    assert np.asarray(st2.logs).tolist() == [[7, 8, 9]]


def test_merged_command_log_replicas_agree():
    """statemachine integration: two replicas fed the same per-group
    decisions in different arrival orders apply the same merged sequence;
    the interleaving audit passes; NOOP skips advance the ring without
    reaching the state machine."""
    rng = np.random.default_rng(0)
    G = 3
    decisions = []
    for g in range(G):
        for i in range(6):
            kind = "NOOP" if (g + i) % 4 == 0 else "STEP"
            decisions.append((g, i, Command(kind, f"b{g}.{i}")))

    def replay(order):
        applied = []
        log = MergedCommandLog(G, apply=lambda c: applied.append(c.arg))
        for g, i, cmd in order:
            log.feed(g, i, cmd)
        return log, applied

    log1, a1 = replay(decisions)
    log2, a2 = replay([decisions[j] for j in rng.permutation(len(decisions))])
    assert a1 == a2
    assert log1.merged == log2.merged
    assert log1.audit() == [] and log2.audit() == []
    # every decision merged, but only non-NOOPs reached the state machine
    assert len(log1.merged) == len(decisions)
    assert len(a1) == sum(1 for _, _, c in decisions if c.kind != "NOOP")
    # conflicting re-decision of an instance must raise (Paxos safety)
    with pytest.raises(AssertionError):
        log1.feed(0, 0, Command("STEP", "other"))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_append_entries_writes_only_k_wide_windows():
    """The append's cost follows K, not the log's capacity: at G=4,
    L=4096, K=512 the only equations with a whole-log [G, L] output are
    the per-group ``dynamic_update_slice`` writes — no gather or select
    over all G·L cells."""
    G, L, K = 4, 4096, 512
    jaxpr = jax.make_jaxpr(M.append_entries)(
        M.init_merge(G, L), jnp.zeros((G, K), jnp.int32),
        jnp.full((G,), K, jnp.int32))
    whole_log = [eqn.primitive.name for eqn in _eqns(jaxpr.jaxpr)
                 if any(getattr(v.aval, "shape", None) == (G, L)
                        for v in eqn.outvars)]
    assert whole_log == ["dynamic_update_slice"] * G
