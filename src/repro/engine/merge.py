"""Deterministic round-robin merge of G per-group ordered logs.

Multi-Ring Paxos' merge function (PAPERS.md [27]) as a pure ``jax.lax``
computation: each ordering group appends its decided ids to a per-group
log; a learner consumes the logs round-robin — round r yields group 0's
r-th entry, then group 1's, ... — which is a *deterministic* interleaving,
so every learner that runs the merge over the same logs derives the same
total order (no cross-group coordination).

Two liveness refinements from the paper carry over:

  * **watermarks** — merge only emits the maximal prefix for which every
    earlier round-robin position is present, so a lagging group blocks
    *later* output but never corrupts order;
  * **explicit skip instances** — an idle group appends ``SKIP`` tokens
    (Multi-Ring's skip messages) that hold a round-robin position but are
    dropped from the merged output, so a slow/idle group cannot stall the
    merged log unboundedly.

Everything is fixed-shape and jit/scan-safe: logs are ``int32[G, L]``
ring-less append buffers with per-group ``watermarks``; the merged prefix
is returned padded with ``PAD``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import stages

SKIP = -2   # explicit null instance: holds a round-robin slot, never emitted
PAD = -1    # padding in fixed-shape outputs / unwritten log tail
RECONFIG = -3  # epoch-boundary marker (repro.engine.epochs): holds one
               # aligned round-robin slot in EVERY group's log at a
               # membership switch, never emitted, never blocks commit —
               # all learners cross the epoch at the same merge position


class MergeState(NamedTuple):
    """Per-group ordered logs plus append watermarks.

    ``overflowed`` counts entries whose append landed past capacity L —
    their log cells were never written even though the watermark advanced,
    so the merged order silently diverges from the oracle beyond that
    point. Any nonzero value means the log was undersized for the run and
    the merged/committed counts are a plateau, not the true order."""
    logs: jax.Array        # int32[G, L] — entries; tail beyond watermark=PAD
    watermarks: jax.Array  # int32[G]    — appended entries per group
    overflowed: jax.Array  # int32[G]    — entries dropped past capacity


def init_merge(groups: int, capacity: int) -> MergeState:
    """Fresh empty merge logs: ``logs`` int32[G, capacity] all PAD,
    zero watermarks/overflow counters. Size ``capacity`` to the total
    entries a run can append per group (ticks × max_entries for
    lock-step runs; passes × K × max_entries under adaptive batching —
    SKIP padding counts against capacity)."""
    return MergeState(
        logs=jnp.full((groups, capacity), PAD, jnp.int32),
        watermarks=jnp.zeros((groups,), jnp.int32),
        overflowed=jnp.zeros((groups,), jnp.int32),
    )


def append_entries(state: MergeState, entries: jax.Array,
                   counts: jax.Array) -> MergeState:
    """Append ``entries[g, :counts[g]]`` to group g's log at its watermark.

    entries: int32[G, K]; counts: int32[G] (0 ≤ counts ≤ K). Pure lax —
    entries past capacity cannot be stored (fixed shapes), but they are no
    longer *silently* dropped: the per-group overflow count accumulates in
    ``state.overflowed`` so callers (and the run_* debug asserts) can
    detect an undersized log instead of consuming a corrupted order.
    """
    with jax.named_scope(stages.MERGE_APPEND):
        G, L = state.logs.shape
        K = entries.shape[1]
        j = jnp.arange(L, dtype=jnp.int32)[None, :]                  # [1, L]
        rel = j - state.watermarks[:, None]                          # [G, L]
        take = (rel >= 0) & (rel < counts[:, None])
        gathered = jnp.take_along_axis(
            entries, jnp.clip(rel, 0, K - 1), axis=1)
        logs = jnp.where(take, gathered, state.logs)
        counts = counts.astype(jnp.int32)
        # entries whose cell index wm+k lands at or past L (watermark may
        # already exceed L from earlier overflow, hence the clip to
        # [0, counts])
        over = jnp.clip(state.watermarks + counts - jnp.int32(L), 0, counts)
        return MergeState(logs=logs,
                          watermarks=state.watermarks + counts,
                          overflowed=state.overflowed + over)


def mergeable_counts(watermarks: jax.Array) -> jax.Array:
    """Per-group count of entries inside the maximal merged prefix.

    Entry (g, i) sits at round-robin position i·G + g; it is emittable iff
    it and every earlier position exist: watermark[g'] ≥ i+1 for g' ≤ g and
    watermark[g'] ≥ i for g' > g. Hence count[g] =
    min(min(wm[0..g]), min(wm[g+1..]) + 1).
    """
    big = jnp.iinfo(jnp.int32).max
    prefix_min = jax.lax.cummin(watermarks)
    suffix_min = jax.lax.cummin(watermarks[::-1])[::-1]
    suffix_after = jnp.concatenate(
        [suffix_min[1:], jnp.array([big], watermarks.dtype)])
    return jnp.minimum(prefix_min, jnp.minimum(suffix_after, big - 1) + 1)


def merged_prefix(state: MergeState) -> tuple[jax.Array, jax.Array]:
    """Maximal merged prefix: (out int32[G·L] padded with PAD, count).

    Control tokens (SKIP, RECONFIG) are dropped (and do not count); order
    is round-robin position order. Idempotent and monotone in the
    watermarks — appending more entries only extends the previously
    returned prefix.
    """
    G, L = state.logs.shape
    counts = mergeable_counts(state.watermarks)                  # [G]
    flat = state.logs.T.reshape(-1)                              # pos = i·G+g
    i_of = jnp.arange(G * L, dtype=jnp.int32) // G
    g_of = jnp.arange(G * L, dtype=jnp.int32) % G
    emit = i_of < counts[g_of]
    keep = emit & (flat >= 0)                   # real ids only, no tokens
    out_idx = jnp.cumsum(keep.astype(jnp.int32)) - 1
    out = jnp.full((G * L,), PAD, jnp.int32)
    out = out.at[jnp.where(keep, out_idx, G * L)].set(flat, mode="drop")
    return out, jnp.sum(keep, dtype=jnp.int32)


def entries_from_assigned(assigned: jax.Array, slot_ids: jax.Array,
                          max_entries: int)\
        -> tuple[jax.Array, jax.Array, jax.Array]:
    """Turn one sharded tick's ``assigned`` output into merge entries.

    assigned: int32[G, W] (per-slot instance assigned this tick, -1 = none);
    slot_ids: int32[G, W] global id of each slot. Returns
    (entries int32[G, max_entries], counts int32[G], dropped int32 scalar)
    where each group's entries are its newly ordered ids in instance
    order, padded to the *per-tick maximum* with SKIP — the explicit null
    instances that keep round-robin positions aligned so an idle group
    never stalls the merge.

    ``max_entries`` must be ≥ the per-tick assignment count (the engine's
    order budget guarantees this); counts are clamped to ``max_entries``
    so an undersized buffer truncates rather than duplicating the last
    kept entry into phantom log positions. Truncation *loses ordered ids*
    — they were assigned instances but never reach the merge log, so the
    commit gate's instance ranks desynchronize from that point on.
    ``dropped`` is the total count of such lost ids this tick; the run_*
    loops accumulate it and debug-assert it stays zero.

    Recycling note: ``slot_ids`` is a *mutable mapping* under window
    recycling — the sharded engine passes its current per-tick slot→id map
    (slots are compacted and refilled between ticks), which is why entries
    snapshot the global id at assignment time. The SKIP-padding discipline
    is unchanged: skip tokens are per-*position* round-robin fillers and
    never refer to slots, so recycling cannot invalidate them.
    """
    with jax.named_scope(stages.MERGE_APPEND):
        mask = assigned >= 0                                         # [G, W]
        pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1         # [G, W]
        n_assigned = jnp.sum(mask, axis=1, dtype=jnp.int32)          # [G]
        entries = jnp.full((assigned.shape[0], max_entries), SKIP, jnp.int32)
        entries = jax.vmap(
            lambda e, p, m, ids: e.at[jnp.where(m, p, max_entries)].set(
                ids, mode="drop"))(entries, pos, mask,
                                   slot_ids.astype(jnp.int32))
        counts = jnp.broadcast_to(
            jnp.minimum(jnp.max(n_assigned), max_entries), n_assigned.shape)
        dropped = jnp.sum(jnp.maximum(n_assigned - max_entries, 0),
                          dtype=jnp.int32)
        return entries, counts, dropped


def round_entries(assigned: jax.Array, slot_ids: jax.Array,
                  round_width: int)\
        -> tuple[jax.Array, jax.Array, jax.Array]:
    """One *fixed-width* merge round per group (adaptive-batching accounting).

    Same extraction as :func:`entries_from_assigned` — each group's newly
    assigned ids in instance order, SKIP-padded — but every group's round
    is exactly ``round_width`` entries wide regardless of what the other
    groups assigned. ``repro.engine.adaptive`` appends one such round per
    group per inner tick, so a group that absorbed k tiles this pass
    appended k·round_width entries while every other group appended the
    same number of (possibly all-SKIP) rounds: round r of group g always
    holds what group g assigned at its r-th tick, which is what makes
    uneven per-group tile consumption merge bit-identically to lock-step
    ticking (cross-group order reduces to lexicographic
    (tick, within-tick index, group) either way — SKIP padding is dropped
    by :func:`merged_prefix` and never reorders real ids).

    assigned: int32[G, W] (-1 = none this tick); slot_ids: int32[G, W].
    Returns (entries int32[G, round_width], n_assigned int32[G],
    dropped int32[G] — ids past ``round_width``, zero whenever
    ``round_width ≥ order_budget``).
    """
    with jax.named_scope(stages.MERGE_APPEND):
        mask = assigned >= 0                                         # [G, W]
        pos = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1         # [G, W]
        n_assigned = jnp.sum(mask, axis=1, dtype=jnp.int32)          # [G]
        entries = jnp.full((assigned.shape[0], round_width), SKIP, jnp.int32)
        entries = jax.vmap(
            lambda e, p, m, ids: e.at[jnp.where(m, p, round_width)].set(
                ids, mode="drop"))(entries, pos, mask,
                                   slot_ids.astype(jnp.int32))
        dropped = jnp.maximum(n_assigned - round_width, 0)
        return entries, n_assigned, dropped


def committed_prefix_len(state: MergeState,
                         decided_by_instance: jax.Array,
                         retired_base: jax.Array | None = None) -> jax.Array:
    """Length of the merged prefix a state machine may *consume*.

    The merged order is defined at assignment time (instance order per
    group), but SMR safety only allows executing entries whose underlying
    instance reached the phase-2b commit quorum. Given
    ``decided_by_instance`` bool[G, C] (instance k of group g committed),
    returns the count of leading emitted entries of ``merged_prefix`` that
    are all committed — consumption stops at the first uncommitted entry;
    skip tokens commit nothing and never block.

    Window recycling (``jaxsim.compact_and_refill_packed``) retires slots
    whose instances form the group's contiguous decided prefix, so a
    recycled engine's live window no longer *contains* those instances.
    ``retired_base`` int32[G] (the per-group monotonic base offset)
    restores them: every instance below the base was decided by
    construction at retirement time, so it is OR-ed into
    ``decided_by_instance`` before the gate runs. ``None`` keeps the
    non-recycled behavior bit-exactly.
    """
    G, L = state.logs.shape
    C = decided_by_instance.shape[1]
    if retired_base is not None:
        decided_by_instance = decided_by_instance | (
            jnp.arange(C, dtype=jnp.int32)[None, :] < retired_base[:, None])
    in_log = jnp.arange(L, dtype=jnp.int32)[None, :] < \
        state.watermarks[:, None]
    # real-id cells only: SKIP and RECONFIG hold positions but carry no
    # instance, commit nothing, and never block
    nonskip = (state.logs >= 0) & in_log
    rank = jnp.cumsum(nonskip.astype(jnp.int32), axis=1) - 1   # instance idx
    ent_dec = jnp.where(
        nonskip,
        jnp.take_along_axis(decided_by_instance,
                            jnp.clip(rank, 0, C - 1), axis=1),
        True)                                                  # tokens: free
    counts = mergeable_counts(state.watermarks)
    i_of = jnp.arange(G * L, dtype=jnp.int32) // G
    g_of = jnp.arange(G * L, dtype=jnp.int32) % G
    emit = i_of < counts[g_of]
    flat = state.logs.T.reshape(-1)
    keep = emit & (flat >= 0)
    dec = ent_dec.T.reshape(-1)
    # barrier: all-committed so far, in round-robin position order
    barrier = jnp.cumprod(jnp.where(emit, dec, True).astype(jnp.int32))
    return jnp.sum((keep & (barrier > 0)).astype(jnp.int32))


# -- pure-python oracle (property-test target) --------------------------------

def oracle_merge(group_logs: list[list[int]]) -> list[int]:
    """Reference merge: strict round-robin over rounds, stop at the first
    missing entry, drop control tokens (SKIP, RECONFIG)."""
    out: list[int] = []
    r = 0
    while True:
        for g in range(len(group_logs)):
            if r >= len(group_logs[g]):
                return out
            e = group_logs[g][r]
            if e >= 0:
                out.append(int(e))
        r += 1


def oracle_is_legal_interleaving(merged: list, group_orders: list[list])\
        -> bool:
    """True iff ``merged`` is a legal interleaving of the per-group orders:
    its restriction to each group's ids equals a prefix of that group's
    order, and it contains no foreign ids. (Canonical checker lives in
    ``repro.core.invariants``; shared with the DES audit.)"""
    from ..core.invariants import check_legal_interleaving
    return not check_legal_interleaving(merged, group_orders)
