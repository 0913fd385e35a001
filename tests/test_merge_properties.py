"""Property tests for ``repro.engine.merge`` skip-instance edge cases
(runnable with real hypothesis or the seeded ``_hypothesis_compat``
shim): a fully-skipped round-robin round must advance watermarks while
emitting nothing, and a group that never appends (empty group) must
bound the merged prefix exactly — both against the pure-python oracle
and through the fixed-shape lax implementation. ``append_entries``,
which rewrites only each group's K-wide window, is held bit-identical to
the whole-log masked append on every field of ``MergeState``."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.engine.merge import (PAD, RECONFIG, SKIP, MergeState,
                                append_entries, init_merge,
                                mergeable_counts, merged_prefix,
                                oracle_merge)


def _merge_rounds(G, rounds, capacity):
    """Append per-round entry lists (len G each) and return the merged
    prefix as a python list."""
    ms = init_merge(G, capacity)
    for rnd in rounds:
        entries = jnp.asarray(np.array(rnd, np.int32)[:, None])
        ms = append_entries(ms, entries, jnp.ones((G,), jnp.int32))
    merged, cnt = merged_prefix(ms)
    return ms, list(np.asarray(merged)[:int(cnt)])


@given(G=st.integers(min_value=1, max_value=5),
       n_rounds=st.integers(min_value=0, max_value=6),
       skip_round=st.integers(min_value=0, max_value=6),
       seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=30, deadline=None)
def test_fully_skipped_round_emits_nothing_but_advances(G, n_rounds,
                                                        skip_round, seed):
    """Inserting an all-SKIP round anywhere changes no emitted entry —
    it only holds round-robin positions (Multi-Ring's skip messages)."""
    rng = np.random.default_rng(seed)
    rounds = [[int(rng.integers(0, 1000)) for _ in range(G)]
              for _ in range(n_rounds)]
    with_skip = list(rounds)
    with_skip.insert(min(skip_round, len(rounds)), [SKIP] * G)
    cap = len(with_skip) + 1
    ms_a, out_a = _merge_rounds(G, rounds, cap)
    ms_b, out_b = _merge_rounds(G, with_skip, cap)
    assert out_b == out_a
    # watermarks advanced through the skip round: one extra entry per group
    assert (np.asarray(ms_b.watermarks)
            == np.asarray(ms_a.watermarks) + 1).all()
    # lax path agrees with the oracle on both logs
    logs_b = [[with_skip[r][g] for r in range(len(with_skip))]
              for g in range(G)]
    assert out_b == oracle_merge(logs_b)


@given(G=st.integers(min_value=2, max_value=5),
       empty_g=st.integers(min_value=0, max_value=4),
       n_rounds=st.integers(min_value=0, max_value=6),
       seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=30, deadline=None)
def test_empty_group_bounds_the_merged_prefix(G, empty_g, n_rounds, seed):
    """A group that never appends caps emission at its round-robin slot:
    groups before it emit their round-0 entry iff they precede it, nothing
    else — exactly the oracle's stop-at-first-missing rule."""
    empty_g = empty_g % G
    rng = np.random.default_rng(seed)
    ms = init_merge(G, n_rounds + 1)
    per_group = [[] if g == empty_g else
                 [int(rng.integers(0, 1000)) for _ in range(n_rounds)]
                 for g in range(G)]
    for r in range(n_rounds):
        entries = np.full((G, 1), SKIP, np.int32)
        counts = np.zeros((G,), np.int32)
        for g in range(G):
            if g != empty_g:
                entries[g, 0] = per_group[g][r]
                counts[g] = 1
        ms = append_entries(ms, jnp.asarray(entries), jnp.asarray(counts))
    merged, cnt = merged_prefix(ms)
    out = list(np.asarray(merged)[:int(cnt)])
    assert out == oracle_merge(per_group)
    # closed form: groups before the empty one emit exactly round 0
    expected = [per_group[g][0] for g in range(empty_g)] if n_rounds else []
    assert out == expected
    # the empty group pins every later group's mergeable count to zero
    counts = np.asarray(mergeable_counts(ms.watermarks))
    assert counts[empty_g] == 0
    assert (counts[empty_g:] == 0).all()
    assert (counts[:empty_g] <= 1).all()
    # tail of the fixed-shape output is PAD
    assert (np.asarray(merged)[int(cnt):] == PAD).all()


@given(G=st.integers(min_value=1, max_value=4),
       seed=st.integers(min_value=0, max_value=10_000),
       n_rounds=st.integers(min_value=1, max_value=5))
@settings(max_examples=30, deadline=None)
def test_mixed_skip_rounds_match_oracle(G, seed, n_rounds):
    """Random per-entry SKIP patterns (partial skip rounds included):
    the lax merge equals the oracle entry for entry."""
    rng = np.random.default_rng(seed)
    rounds = [[SKIP if rng.random() < 0.4 else int(rng.integers(0, 1000))
               for _ in range(G)] for _ in range(n_rounds)]
    _, out = _merge_rounds(G, rounds, n_rounds + 1)
    logs = [[rounds[r][g] for r in range(n_rounds)] for g in range(G)]
    assert out == oracle_merge(logs)


def _dense_append(state, entries, counts):
    """Reference append over the whole log: each of the G·L cells gathers
    from ``entries`` and keeps its old value unless it lies in
    ``[watermark, watermark + counts)``."""
    G, L = state.logs.shape
    K = entries.shape[1]
    j = jnp.arange(L, dtype=jnp.int32)[None, :]                  # [1, L]
    rel = j - state.watermarks[:, None]                          # [G, L]
    take = (rel >= 0) & (rel < counts[:, None])
    gathered = jnp.take_along_axis(
        entries, jnp.clip(rel, 0, K - 1), axis=1)
    logs = jnp.where(take, gathered, state.logs)
    counts = counts.astype(jnp.int32)
    over = jnp.clip(state.watermarks + counts - jnp.int32(L), 0, counts)
    return MergeState(logs=logs,
                      watermarks=state.watermarks + counts,
                      overflowed=state.overflowed + over)


def _entries(rng, G, K):
    """Random ids mixed with SKIP and RECONFIG tokens."""
    e = rng.integers(0, 1000, (G, K)).astype(np.int32)
    tok = rng.random((G, K))
    e[tok < 0.2] = SKIP
    e[tok > 0.9] = RECONFIG
    return e


def _chained(seed):
    """Random G, L, K (K > L included) and up to 9 appends from empty."""
    rng = np.random.default_rng(seed)
    G, L, K = (int(rng.integers(1, 5)), int(rng.integers(1, 41)),
               int(rng.integers(1, 13)))
    steps = [(_entries(rng, G, K), rng.integers(0, K + 1, G))
             for _ in range(int(rng.integers(1, 10)))]
    return init_merge(G, L), steps


def _boundary_watermarks():
    """One group per landing of the watermark: below, at, across and past
    L − K, and at and past L; full and empty appends in turn."""
    rng = np.random.default_rng(1)
    L, K = 20, 6
    wm = np.array([0, L - K - 1, L - K, L - K + 1, L - 2, L - 1, L, L + 3],
                  np.int32)
    G = len(wm)
    state = MergeState(
        logs=jnp.asarray(_entries(rng, G, L)), watermarks=jnp.asarray(wm),
        overflowed=jnp.asarray(np.maximum(wm - L, 0).astype(np.int32)))
    steps = [(_entries(rng, G, K), np.full(G, c)) for c in (K, 0, K, 1, K)]
    return state, steps


def _counts_zero_and_full():
    """Each group appends either nothing or all K entries, every round."""
    rng = np.random.default_rng(2)
    G, L, K = 4, 24, 5
    steps = [(_entries(rng, G, K), rng.choice([0, K], G)) for _ in range(7)]
    return init_merge(G, L), steps


def _wider_than_log():
    """K > L: the whole row is the append's window."""
    rng = np.random.default_rng(3)
    G, L, K = 3, 5, 8
    steps = [(_entries(rng, G, K), rng.integers(0, K + 1, G))
             for _ in range(4)]
    return init_merge(G, L), steps


def _adaptive_width():
    """The adaptive engine's wide append: R ≤ K rounds of rw entries each,
    one buffer K·rw wide, every group appending R·rw."""
    rng = np.random.default_rng(4)
    G, K, rw = 3, 4, 3
    L = 5 * K * rw // 2
    steps = [(_entries(rng, G, K * rw), np.full(G, int(rng.integers(0, K + 1))
                                                * rw)) for _ in range(6)]
    return init_merge(G, L), steps


APPEND_CASES = {**{f"chained{s}": (lambda s=s: _chained(s)) for s in range(8)},
                "boundary_watermarks": _boundary_watermarks,
                "counts_zero_and_full": _counts_zero_and_full,
                "wider_than_log": _wider_than_log,
                "adaptive_width": _adaptive_width}


@pytest.mark.parametrize("case", list(APPEND_CASES))
def test_append_entries_matches_dense_append(case):
    """The windowed append leaves logs, watermarks and overflow counts
    bit-identical to the whole-log masked append after every step."""
    state, steps = APPEND_CASES[case]()
    dense = state
    for entries, counts in steps:
        e = jnp.asarray(entries, jnp.int32)
        c = jnp.asarray(counts, jnp.int32)
        state = append_entries(state, e, c)
        dense = _dense_append(dense, e, c)
        for got, want in zip(state, dense):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
