"""G independent quorum/ordering windows batched along a leading group axis.

Each group runs exactly the single-group machinery of
``repro.core.jaxsim`` (its un-jitted packed cores) — ``jax.vmap`` along a
new leading ``G`` axis turns the G per-group ticks into one fused XLA
computation over ``uint32[G, W, WORDS]`` bitsets, and
``repro.kernels.quorum.quorum_update_grouped`` is the matching 2-D-grid
Pallas kernel for the absorb/stabilize step. G=1 is bit-identical to
``jaxsim.engine_tick`` by construction (same core functions, vmapped over
a singleton axis).

Why sharding multiplies throughput (§5.1, Multi-Ring): each group has its
*own* leader whose ordering rate is bounded per tick
(``order_budget`` ≈ pipeline_depth × order_batch_max of classic.py), so at
equal total window G groups drain a backlog G× faster. The per-group
orders are merged into the single learner-facing total order by
``repro.engine.merge`` (deterministic round-robin with explicit skips).

**Window recycling** (``RecycleState`` + the ``recycled_*`` family): the
plain engine's slots are single-use — once a window's ids are decided,
throughput collapses to zero until re-init, so only a cold burst is ever
measured. The recycled engine wraps the same per-group cores with
``jaxsim.compact_and_refill_packed``: whenever a group's free-slot count
drops below a watermark, its contiguous decided instance prefix is
retired, live slots shift down, and the freed tail is refilled with fresh
slots carrying new monotone ids — so a long-running engine sustains
ordering throughput across unbounded window generations. Recycling is
pure host-side slot remapping around the quorum math: the grouped Pallas
kernel (``repro.kernels.quorum.quorum_update_grouped``) sees only dense
``uint32[G, W, WORDS]`` tiles and stays completely oblivious to it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import jaxsim
from ..core.jaxsim import QuorumState
from ..dissem.engine import DissemState, absorb_holds_packed, init_dissem
from . import merge as merge_mod
from . import stages


def init_sharded(groups: int, window: int, n_diss: int, n_seq: int)\
        -> QuorumState:
    """QuorumState pytree with a leading group axis: uint32[G, W, WORDS]."""
    single = jaxsim.init_state(window, n_diss, n_seq)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (groups,) + x.shape), single)


def default_slot_ids(groups: int, window: int) -> jax.Array:
    """Global id of slot (g, w): g·W + w (int32[G, W])."""
    return (jnp.arange(groups, dtype=jnp.int32)[:, None] * window
            + jnp.arange(window, dtype=jnp.int32)[None, :])


@functools.partial(jax.jit, static_argnames=("diss_majority", "seq_majority",
                                             "order_budget"))
def sharded_tick(state: QuorumState, packed_acks: jax.Array,
                 packed_votes: jax.Array, *, diss_majority: int,
                 seq_majority: int, order_budget: int | None = None)\
        -> tuple[QuorumState, dict]:
    """One fused tick of all G groups over packed uint32 tiles.

    state: leading-G QuorumState; packed_acks: uint32[G, W, WORDS_D];
    packed_votes: uint32[G, W, WORDS_S]. Returns (state, out) with
    out["assigned"] int32[G, W] / out["newly_decided"] bool[G, W].
    """
    body = functools.partial(jaxsim.engine_tick_packed,
                             diss_majority=diss_majority,
                             seq_majority=seq_majority,
                             order_budget=order_budget)
    return jax.vmap(body)(state, packed_acks, packed_votes)


@functools.partial(jax.jit, static_argnames=("diss_majority", "seq_majority",
                                             "order_budget"))
def sharded_tick_dense(state: QuorumState, acks: jax.Array,
                       votes: jax.Array, *, diss_majority: int,
                       seq_majority: int, order_budget: int | None = None)\
        -> tuple[QuorumState, dict]:
    """Bool-tile convenience wrapper (acks bool[G, W, D], votes
    bool[G, W, S]) — the interface of ``jaxsim.engine_tick`` with a group
    axis, used by the G=1 bit-identity regression tests."""
    return sharded_tick(state, jax.vmap(jaxsim.pack_tile)(acks),
                        jax.vmap(jaxsim.pack_tile)(votes),
                        diss_majority=diss_majority,
                        seq_majority=seq_majority,
                        order_budget=order_budget)


def run_sharded_ticks(state: QuorumState, packed_acks_seq: jax.Array,
                      packed_votes_seq: jax.Array, *, diss_majority: int,
                      seq_majority: int, order_budget: int | None = None)\
        -> tuple[QuorumState, dict]:
    """lax.scan over T fused ticks of [T, G, W, WORDS] packed traffic."""
    body_fn = functools.partial(jaxsim.engine_tick_packed,
                                diss_majority=diss_majority,
                                seq_majority=seq_majority,
                                order_budget=order_budget)
    vtick = jax.vmap(body_fn)

    def body(st, tv):
        a, v = tv
        return vtick(st, a, v)
    return jax.lax.scan(body, state, (packed_acks_seq, packed_votes_seq))


def _resolve_max_entries(max_entries: int | None,
                         order_budget: int) -> int:
    """Default and validate the per-tick merge buffer width. Raises (not
    assert: the failure mode is silent merged-log corruption that
    desynchronizes the commit gate's instance ranks, which must not be
    compiled out under ``python -O``)."""
    if max_entries is None:
        return order_budget
    if max_entries < order_budget:
        raise ValueError(
            f"max_entries={max_entries} < order_budget={order_budget}: a "
            "tick could assign more ids than the merge buffer holds — "
            "truncated entries desynchronize the commit gate's instance "
            "ranks and can let it consume uncommitted ids")
    return max_entries


def _assert_no_dropped(dropped) -> None:
    """jax.debug.callback target: the run_* scans accumulate the per-tick
    over-assignment drop count from ``entries_from_assigned``. It is zero
    whenever ``max_entries ≥ order_budget`` (``_resolve_max_entries``
    enforces that statically), so this firing means an engine invariant
    broke — ordered ids never reached the merge log."""
    if int(dropped) != 0:
        raise AssertionError(
            f"{int(dropped)} ordered ids were truncated out of the merge "
            "entries (over-assignment past max_entries) — the merged order "
            "is missing ids and the commit gate's instance ranks are "
            "desynchronized")


# the quorum state and merge log are donated: a fused run rewrites both
# wholesale and callers thread the returned pair, so the inputs are dead
# on return (re-reading them raises jax's deleted-buffer error rather
# than showing stale data).  slot_ids and the traffic sequences are NOT
# donated — callers legitimately reuse them across runs.
@functools.partial(jax.jit, static_argnames=("diss_majority", "seq_majority",
                                             "order_budget", "max_entries"),
                   donate_argnums=(0, 1))
def run_sharded_ticks_merged(state: QuorumState, merge_state,
                             packed_acks_seq: jax.Array,
                             packed_votes_seq: jax.Array,
                             slot_ids: jax.Array, *, diss_majority: int,
                             seq_majority: int, order_budget: int,
                             max_entries: int | None = None)\
        -> tuple[QuorumState, "merge_mod.MergeState", jax.Array, jax.Array,
                 jax.Array]:
    """Fused hot loop: tick all groups AND feed the deterministic merge.

    Per tick, each group's newly assigned ids (in instance order) are
    appended to its merge log, padded to the per-tick maximum with SKIP
    tokens so a slow group cannot stall the merged prefix. Returns
    (final engine state, final merge state, merged int32[G·L] padded,
    merged_count, committed_count): ``merged[:merged_count]`` is the
    single total *order* across all groups (defined at assignment time);
    only ``merged[:committed_count]`` — the leading entries whose
    instances reached the phase-2b commit quorum — may be consumed by the
    state machine.
    """
    max_entries = _resolve_max_entries(max_entries, order_budget)
    body_fn = functools.partial(jaxsim.engine_tick_packed,
                                diss_majority=diss_majority,
                                seq_majority=seq_majority,
                                order_budget=order_budget)
    vtick = jax.vmap(body_fn)

    def body(carry, tv):
        st, ms, dropped = carry
        a, v = tv
        st, out = vtick(st, a, v)
        entries, counts, d_t = merge_mod.entries_from_assigned(
            out["assigned"], slot_ids, max_entries)
        ms = merge_mod.append_entries(ms, entries, counts)
        return (st, ms, dropped + d_t), ()

    (state, merge_state, dropped), _ = jax.lax.scan(
        body, (state, merge_state, jnp.int32(0)),
        (packed_acks_seq, packed_votes_seq))
    jax.debug.callback(_assert_no_dropped, dropped)
    merged, count = merge_mod.merged_prefix(merge_state)
    # commit gate: instance k of group g is consumable once its slot's 2b
    # quorum is in — scatter per-slot decided flags into instance order
    dec_by_inst = _decided_by_instance(state.instance, state.decided,
                                       merge_state.logs.shape[1])
    committed = merge_mod.committed_prefix_len(merge_state, dec_by_inst)
    return state, merge_state, merged, count, committed


def _decided_by_instance(instance: jax.Array, decided: jax.Array,
                         capacity: int) -> jax.Array:
    """Scatter per-slot decided flags into instance order: bool[G, C] with
    entry (g, k) True iff instance k of group g is decided *in the live
    window* (retired instances are the caller's business — see
    ``committed_prefix_len(retired_base=...)``)."""
    return jax.vmap(
        lambda inst, dec: jnp.zeros((capacity,), jnp.bool_).at[
            jnp.where(inst >= 0, inst, capacity)].set(dec, mode="drop"))(
        instance, decided)


# -- window recycling ---------------------------------------------------------

class RecycleState(NamedTuple):
    """Sharded engine state plus the recycling bookkeeping.

    ``q`` is the leading-G :class:`QuorumState` (exactly what the plain
    sharded engine ticks — the quorum math and the Pallas kernel never see
    the recycling); ``slot_ids`` maps slot (g, w) to the global id it
    currently holds; ``retired`` is each group's monotonic base offset:
    the count of instances (== slots) retired so far, below which every
    instance is known-decided."""
    q: QuorumState          # leading-G pytree
    slot_ids: jax.Array     # int32[G, W]
    retired: jax.Array      # int32[G]


def init_recycled(groups: int, window: int, n_diss: int, n_seq: int,
                  *, id_stride: int | None = None) -> RecycleState:
    """Fresh recycled engine. Group g owns the id range
    ``[g·id_stride, (g+1)·id_stride)``; ids are issued monotonically from
    the bottom of the range as slots are recycled, so ``id_stride`` must
    exceed the total ids a group will ever admit (``W + retired`` grows
    without bound and is never range-checked on the jit path — an
    undersized stride silently collides with the next group's ids).
    With a single group there is no next group, so ``None`` defaults to
    ``window`` (ids are monotone within the group and never reused);
    with G > 1 the stride bounds the run length, so it must be explicit.
    """
    if id_stride is None:
        if groups > 1:
            raise ValueError(
                "init_recycled(groups>1) needs an explicit id_stride: "
                "recycling issues fresh ids past g*id_stride + window, so "
                "a defaulted stride of `window` would collide with the "
                "next group's id range at the first recycle")
        id_stride = window
    ids = (jnp.arange(groups, dtype=jnp.int32)[:, None] * id_stride
           + jnp.arange(window, dtype=jnp.int32)[None, :])
    return RecycleState(q=init_sharded(groups, window, n_diss, n_seq),
                        slot_ids=ids,
                        retired=jnp.zeros((groups,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("watermark", "id_stride"))
def recycle_groups(rs: RecycleState, *, watermark: int, id_stride: int,
                   id_base: jax.Array | None = None)\
        -> tuple[RecycleState, jax.Array]:
    """Per-group watermark-gated compaction/refill (one fused vmap).

    A group recycles only when its free-slot count — slots still doing
    useful work, i.e. not yet decided — drops below ``watermark`` AND its
    frontier head (the slot holding instance ``retired``) is decided, so
    something would actually retire; the check gates
    ``jaxsim.compact_and_refill_packed`` per group, so busy groups
    amortize the compaction shuffle over many ticks while idle groups are
    bit-exact no-ops. Ticks where no group passes both gates skip the
    compaction scatters entirely (``lax.cond``) — including the stalled
    case where one undecided old instance pins the frontier — so the
    amortization is real compute savings, not just a masked no-op.
    Returns (state', n_retired int32[G]).

    ``id_base`` int32[rows] overrides the per-row fresh-id range base
    (default: row index × ``id_stride``).  The meshed engine passes each
    device's *global* group offsets here — a device's local row 0 is not
    logical group 0, and fresh ids must come from the logical group's
    private range no matter which device owns the row.
    """
    with jax.named_scope(stages.RECYCLE):
        G = rs.slot_ids.shape[0]
        free = jnp.sum(~rs.q.decided, axis=1, dtype=jnp.int32)
        head_retirable = jnp.any(
            (rs.q.instance == rs.retired[:, None]) & rs.q.decided, axis=1)
        enable = (free < watermark) & head_retirable
        if id_base is None:
            id_base = jnp.arange(G, dtype=jnp.int32) * id_stride

        def compact(rs):
            q, ids, retired, n_ret = jax.vmap(
                jaxsim.compact_and_refill_packed)(
                rs.q, rs.slot_ids, rs.retired, id_base, enable)
            return RecycleState(q=q, slot_ids=ids, retired=retired), n_ret

        def skip(rs):
            return rs, jnp.zeros((G,), jnp.int32)

        return jax.lax.cond(jnp.any(enable), compact, skip, rs)


def recycled_committed_prefix(rs: RecycleState,
                              merge_state: "merge_mod.MergeState")\
        -> tuple[jax.Array, jax.Array, jax.Array]:
    """(merged int32[G·L] padded, merged_count, committed_count) for a
    recycled engine: the commit gate recovers decided flags of retired
    instances from the base offset (``committed_prefix_len`` with
    ``retired_base``) and of live instances from the window."""
    live = _decided_by_instance(rs.q.instance, rs.q.decided,
                                merge_state.logs.shape[1])
    merged, count = merge_mod.merged_prefix(merge_state)
    committed = merge_mod.committed_prefix_len(merge_state, live,
                                               retired_base=rs.retired)
    return merged, count, committed


def _recycled_body(rs: RecycleState, merge_state, packed_acks, packed_votes,
                   *, diss_majority, seq_majority, order_budget, max_entries,
                   watermark, id_stride):
    """One sustained-engine step: tick → append to merge → recycle.

    Ordering matters: entries must reach the merge log *before* their
    slots can be retired (a decided slot's log entry is what the commit
    gate consumes once the slot is gone)."""
    vtick = jax.vmap(functools.partial(
        jaxsim.engine_tick_packed, diss_majority=diss_majority,
        seq_majority=seq_majority, order_budget=order_budget))
    q, out = vtick(rs.q, packed_acks, packed_votes)
    entries, counts, dropped = merge_mod.entries_from_assigned(
        out["assigned"], rs.slot_ids, max_entries)
    merge_state = merge_mod.append_entries(merge_state, entries, counts)
    rs = RecycleState(q=q, slot_ids=rs.slot_ids, retired=rs.retired)
    rs, n_ret = recycle_groups(rs, watermark=watermark, id_stride=id_stride)
    out = dict(out, n_retired=n_ret, dropped=dropped)
    return rs, merge_state, out


@functools.partial(jax.jit, static_argnames=(
    "diss_majority", "seq_majority", "order_budget", "max_entries",
    "watermark", "id_stride"))
def recycled_tick_merged(rs: RecycleState, merge_state,
                         packed_acks: jax.Array, packed_votes: jax.Array,
                         *, diss_majority: int, seq_majority: int,
                         order_budget: int, max_entries: int | None = None,
                         watermark: int, id_stride: int)\
        -> tuple[RecycleState, "merge_mod.MergeState", dict]:
    """Single-step entry point of the sustained engine (the scan body of
    ``run_recycled_ticks_merged``), for host-driven loops that must read
    ``rs.slot_ids`` back between ticks — e.g. traffic generators that
    address ids, not slots."""
    max_entries = _resolve_max_entries(max_entries, order_budget)
    return _recycled_body(rs, merge_state, packed_acks, packed_votes,
                          diss_majority=diss_majority,
                          seq_majority=seq_majority,
                          order_budget=order_budget, max_entries=max_entries,
                          watermark=watermark, id_stride=id_stride)


@functools.partial(jax.jit, static_argnames=(
    "diss_majority", "seq_majority", "order_budget", "max_entries",
    "watermark", "id_stride"), donate_argnums=(0, 1))
def run_recycled_ticks_merged(rs: RecycleState, merge_state,
                              packed_acks_seq: jax.Array,
                              packed_votes_seq: jax.Array, *,
                              diss_majority: int, seq_majority: int,
                              order_budget: int,
                              max_entries: int | None = None,
                              watermark: int, id_stride: int)\
        -> tuple[RecycleState, "merge_mod.MergeState", jax.Array,
                 jax.Array, jax.Array]:
    """Fused sustained hot loop: scan T recycled steps, then gate.

    Same shapes and return contract as ``run_sharded_ticks_merged``, but
    the engine state is a :class:`RecycleState` and slots are recycled
    between ticks, so the loop can run for arbitrarily many window
    generations — call it repeatedly with the carried (rs, merge_state)
    to measure sustained throughput segment by segment. Returns
    (rs, merge_state, merged, merged_count, committed_count).

    Traffic addressing caveat: tiles index slots by *position*, and
    recycling remaps position→id mid-scan where the caller cannot observe
    ``rs.slot_ids``. Only position-uniform traffic (e.g. saturated
    backlog tiles, every live slot treated alike) is sound here; a
    traffic source that addresses specific *ids* must drive
    ``recycled_tick_merged`` one step at a time and rebuild its tiles
    from the live ``rs.slot_ids`` between ticks.

    Capacity bound: recycling unbounds the *window*, not the merge log —
    ``merge_state`` must be sized for the whole run (per-group capacity ≥
    total appended entries, ≤ ticks × max_entries). Writes past capacity
    cannot be stored while watermarks keep advancing, so an undersized
    log plateaus the merged/committed counts — ``merge_state.overflowed``
    counts exactly those lost entries per group (check it between
    segments); long-lived services should checkpoint and re-init the log
    between segments (log compaction is the merge-side sibling of window
    recycling).
    """
    max_entries = _resolve_max_entries(max_entries, order_budget)
    body_kw = dict(diss_majority=diss_majority, seq_majority=seq_majority,
                   order_budget=order_budget, max_entries=max_entries,
                   watermark=watermark, id_stride=id_stride)

    def body(carry, tv):
        rs, ms, dropped = carry
        a, v = tv
        rs, ms, out = _recycled_body(rs, ms, a, v, **body_kw)
        return (rs, ms, dropped + out["dropped"]), ()

    (rs, merge_state, dropped), _ = jax.lax.scan(
        body, (rs, merge_state, jnp.int32(0)),
        (packed_acks_seq, packed_votes_seq))
    jax.debug.callback(_assert_no_dropped, dropped)
    merged, count, committed = recycled_committed_prefix(rs, merge_state)
    return rs, merge_state, merged, count, committed


# -- dissemination-stability gating -------------------------------------------
#
# HT-Paxos orders *ids*, but an id may only be proposed for ordering once
# its batch is durable — a majority of the group's disseminator partition
# holds the payload (§4.1 step 36's precondition via steps 15–20). The
# plain engine above assumes that precondition away (every id is born
# orderable); the gated family threads a ``repro.dissem`` DissemState
# alongside the QuorumState and masks each slot's phase-2b votes until the
# dissemination layer marks its id stable. With every id pre-stable
# (``init_dissem(pre_stable=True)``, or saturated hold tiles) the mask is
# the identity and the gated engine is bit-identical to the ungated one —
# the regression baseline the tests pin down, including under recycling.


def _gated_votes(d: DissemState, packed_votes: jax.Array) -> jax.Array:
    """Zero the vote tile of every not-yet-stable slot. Votes are masked,
    not buffered: DES sequencers re-multicast 2b for pending instances
    each round, so dropped votes reappear once the id stabilizes."""
    return jnp.where(d.stable[..., None], packed_votes, jnp.uint32(0))


def _gated_step(q: QuorumState, d: DissemState, packed_acks: jax.Array,
                packed_holds: jax.Array, packed_votes: jax.Array, *,
                diss_majority: int, seq_majority: int, stab_majority: int,
                order_budget: int | None)\
        -> tuple[QuorumState, DissemState, dict]:
    """The gated families' shared step, merge and recycling aside: absorb
    the holds (stability), then tick every group's window with its votes
    masked until stable (ordering). Returns (q, d, out) with
    ``engine_tick_packed``'s outputs plus out["newly_stable"] bool[G, W]."""
    with jax.named_scope(stages.STABILITY):
        d, dout = absorb_holds_packed(d, packed_holds, stab_majority)
    with jax.named_scope(stages.ORDERING):
        q, out = jax.vmap(functools.partial(
            jaxsim.engine_tick_packed, diss_majority=diss_majority,
            seq_majority=seq_majority, order_budget=order_budget))(
            q, packed_acks, _gated_votes(d, packed_votes))
    return q, d, dict(out, newly_stable=dout["newly_stable"])


@functools.partial(jax.jit, static_argnames=(
    "diss_majority", "seq_majority", "stab_majority", "order_budget"))
def gated_tick(state: QuorumState, d: DissemState, packed_acks: jax.Array,
               packed_holds: jax.Array, packed_votes: jax.Array, *,
               diss_majority: int, seq_majority: int, stab_majority: int,
               order_budget: int | None = None)\
        -> tuple[QuorumState, DissemState, dict]:
    """One fused tick of dissemination + ordering across all G groups.

    packed_holds: uint32[G, W, WORDS_DP] batch-delivery bits for the
    group's disseminator *partition* (stab_majority is a majority of that
    partition). Holds absorb **before** votes are masked, so a vote
    arriving in the same tick as the stabilizing delivery counts — the
    gate adds no latency beyond the dissemination itself. Returns
    (state, d, out) with the ungated tick's outputs plus
    out["newly_stable"] bool[G, W]."""
    return _gated_step(state, d, packed_acks, packed_holds, packed_votes,
                       diss_majority=diss_majority,
                       seq_majority=seq_majority,
                       stab_majority=stab_majority,
                       order_budget=order_budget)


@functools.partial(jax.jit, static_argnames=(
    "diss_majority", "seq_majority", "stab_majority", "order_budget",
    "max_entries"), donate_argnums=(0, 1, 2))
def run_gated_ticks_merged(state: QuorumState, d: DissemState, merge_state,
                           packed_acks_seq: jax.Array,
                           packed_holds_seq: jax.Array,
                           packed_votes_seq: jax.Array,
                           slot_ids: jax.Array, *, diss_majority: int,
                           seq_majority: int, stab_majority: int,
                           order_budget: int,
                           max_entries: int | None = None)\
        -> tuple[QuorumState, DissemState, "merge_mod.MergeState",
                 jax.Array, jax.Array, jax.Array]:
    """``run_sharded_ticks_merged`` with the stability gate in the loop:
    scan T ticks of (acks, holds, votes) traffic, feed the deterministic
    merge, then apply the commit gate. Returns
    (state, d, merge_state, merged, merged_count, committed_count)."""
    max_entries = _resolve_max_entries(max_entries, order_budget)

    def body(carry, tv):
        st, d, ms, dropped = carry
        a, h, v = tv
        st, d, out = _gated_step(st, d, a, h, v,
                                 diss_majority=diss_majority,
                                 seq_majority=seq_majority,
                                 stab_majority=stab_majority,
                                 order_budget=order_budget)
        entries, counts, d_t = merge_mod.entries_from_assigned(
            out["assigned"], slot_ids, max_entries)
        ms = merge_mod.append_entries(ms, entries, counts)
        return (st, d, ms, dropped + d_t), ()

    (state, d, merge_state, dropped), _ = jax.lax.scan(
        body, (state, d, merge_state, jnp.int32(0)),
        (packed_acks_seq, packed_holds_seq, packed_votes_seq))
    jax.debug.callback(_assert_no_dropped, dropped)
    merged, count = merge_mod.merged_prefix(merge_state)
    dec_by_inst = _decided_by_instance(state.instance, state.decided,
                                       merge_state.logs.shape[1])
    committed = merge_mod.committed_prefix_len(merge_state, dec_by_inst)
    return state, d, merge_state, merged, count, committed


class GatedRecycleState(NamedTuple):
    """Sustained gated engine: the recycled ordering state plus its
    lockstep dissemination window — slot (g, w) of ``d`` always tracks
    the id in ``rs.slot_ids[g, w]``; recycling compacts both with one
    shared :class:`jaxsim.CompactionPlan` per group."""
    rs: RecycleState
    d: DissemState


def init_gated_recycled(groups: int, window: int, n_diss: int, n_seq: int,
                        *, n_diss_partition: int | None = None,
                        id_stride: int | None = None,
                        pre_stable: bool = False) -> GatedRecycleState:
    """Fresh sustained gated engine. ``n_diss_partition`` sizes the hold
    bitsets (the per-group disseminator partition, m/G; defaults to
    ``n_diss`` — the ungated engine's disseminator count doubling as a
    global set)."""
    if n_diss_partition is None:
        n_diss_partition = n_diss
    return GatedRecycleState(
        rs=init_recycled(groups, window, n_diss, n_seq,
                         id_stride=id_stride),
        d=init_dissem(groups, window, n_diss_partition,
                      pre_stable=pre_stable))


@functools.partial(jax.jit, static_argnames=("watermark", "id_stride",
                                             "fresh_stable"))
def gated_recycle_groups(gs: GatedRecycleState, *, watermark: int,
                         id_stride: int, fresh_stable: bool = False,
                         id_base: jax.Array | None = None)\
        -> tuple[GatedRecycleState, jax.Array]:
    """``recycle_groups`` for the gated engine: one shared per-group
    compaction plan moves the quorum window AND the dissemination window,
    so retired slots release their hold bitsets (zeroed) and stability
    flags in the same shuffle. Releasing is safe by construction: only
    decided instances retire, and a decided id passed the gate, so its
    dissemination state is spent. Freed slots are born with empty holds
    and ``stable=fresh_stable`` (False models real traffic — a fresh id
    must re-earn stability; True preserves the all-pre-stable
    bit-identity baseline across recycles).

    ``id_base`` overrides the per-row fresh-id range base exactly as in
    :func:`recycle_groups` (the meshed engine's global-offset hook)."""
    with jax.named_scope(stages.RECYCLE):
        G = gs.rs.slot_ids.shape[0]
        free = jnp.sum(~gs.rs.q.decided, axis=1, dtype=jnp.int32)
        head_retirable = jnp.any(
            (gs.rs.q.instance == gs.rs.retired[:, None]) & gs.rs.q.decided,
            axis=1)
        enable = (free < watermark) & head_retirable
        if id_base is None:
            id_base = jnp.arange(G, dtype=jnp.int32) * id_stride

        def compact(gs):
            def per_group(q, ids, retired, base, en, holds, stab):
                plan = jaxsim.compaction_plan(q, retired, en)
                q, ids, retired, n_ret = jaxsim.compact_and_refill_packed(
                    q, ids, retired, base, plan=plan)
                holds = jaxsim.apply_compaction(plan, holds, jnp.uint32(0))
                stab = jaxsim.apply_compaction(plan, stab, fresh_stable)
                return q, ids, retired, n_ret, holds, stab
            q, ids, retired, n_ret, holds, stab = jax.vmap(per_group)(
                gs.rs.q, gs.rs.slot_ids, gs.rs.retired, id_base, enable,
                gs.d.hold_bits, gs.d.stable)
            return (GatedRecycleState(
                rs=RecycleState(q=q, slot_ids=ids, retired=retired),
                d=DissemState(hold_bits=holds, stable=stab)), n_ret)

        def skip(gs):
            return gs, jnp.zeros((G,), jnp.int32)

        return jax.lax.cond(jnp.any(enable), compact, skip, gs)


def _gated_recycled_body(gs: GatedRecycleState, merge_state, packed_acks,
                         packed_holds, packed_votes, *, diss_majority,
                         seq_majority, stab_majority, order_budget,
                         max_entries, watermark, id_stride, fresh_stable):
    """One sustained gated step: absorb holds → gated tick → append to
    merge → recycle both windows (same ordering rationale as
    ``_recycled_body``; holds absorb first so a recycled slot saturated
    by this tick's hold tile is already stable at vote time)."""
    q, d, out = _gated_step(gs.rs.q, gs.d, packed_acks, packed_holds,
                            packed_votes, diss_majority=diss_majority,
                            seq_majority=seq_majority,
                            stab_majority=stab_majority,
                            order_budget=order_budget)
    entries, counts, dropped = merge_mod.entries_from_assigned(
        out["assigned"], gs.rs.slot_ids, max_entries)
    merge_state = merge_mod.append_entries(merge_state, entries, counts)
    gs = GatedRecycleState(
        rs=RecycleState(q=q, slot_ids=gs.rs.slot_ids,
                        retired=gs.rs.retired), d=d)
    gs, n_ret = gated_recycle_groups(gs, watermark=watermark,
                                     id_stride=id_stride,
                                     fresh_stable=fresh_stable)
    out = dict(out, n_retired=n_ret, dropped=dropped)
    return gs, merge_state, out


@functools.partial(jax.jit, static_argnames=(
    "diss_majority", "seq_majority", "stab_majority", "order_budget",
    "max_entries", "watermark", "id_stride", "fresh_stable"))
def gated_recycled_tick_merged(gs: GatedRecycleState, merge_state,
                               packed_acks: jax.Array,
                               packed_holds: jax.Array,
                               packed_votes: jax.Array, *,
                               diss_majority: int, seq_majority: int,
                               stab_majority: int, order_budget: int,
                               max_entries: int | None = None,
                               watermark: int, id_stride: int,
                               fresh_stable: bool = False)\
        -> tuple[GatedRecycleState, "merge_mod.MergeState", dict]:
    """Single-step entry point of the sustained gated engine — the
    host-driven twin of ``recycled_tick_merged`` for traffic sources that
    address ids and must re-read ``gs.rs.slot_ids`` between ticks (the
    DES replay does exactly this)."""
    max_entries = _resolve_max_entries(max_entries, order_budget)
    return _gated_recycled_body(
        gs, merge_state, packed_acks, packed_holds, packed_votes,
        diss_majority=diss_majority, seq_majority=seq_majority,
        stab_majority=stab_majority, order_budget=order_budget,
        max_entries=max_entries, watermark=watermark, id_stride=id_stride,
        fresh_stable=fresh_stable)


@functools.partial(jax.jit, static_argnames=(
    "diss_majority", "seq_majority", "stab_majority", "order_budget",
    "max_entries", "watermark", "id_stride", "fresh_stable"),
    donate_argnums=(0, 1))
def run_gated_recycled_ticks_merged(gs: GatedRecycleState, merge_state,
                                    packed_acks_seq: jax.Array,
                                    packed_holds_seq: jax.Array,
                                    packed_votes_seq: jax.Array, *,
                                    diss_majority: int, seq_majority: int,
                                    stab_majority: int, order_budget: int,
                                    max_entries: int | None = None,
                                    watermark: int, id_stride: int,
                                    fresh_stable: bool = False)\
        -> tuple[GatedRecycleState, "merge_mod.MergeState", jax.Array,
                 jax.Array, jax.Array]:
    """Fused sustained gated hot loop: scan T gated recycled steps, then
    gate the merged prefix. Same return contract and traffic-addressing /
    merge-capacity caveats as ``run_recycled_ticks_merged``; the extra
    leading input is uint32[T, G, W, WORDS_DP] hold traffic."""
    max_entries = _resolve_max_entries(max_entries, order_budget)
    body_kw = dict(diss_majority=diss_majority, seq_majority=seq_majority,
                   stab_majority=stab_majority, order_budget=order_budget,
                   max_entries=max_entries, watermark=watermark,
                   id_stride=id_stride, fresh_stable=fresh_stable)

    def body(carry, tv):
        gs, ms, dropped = carry
        a, h, v = tv
        gs, ms, out = _gated_recycled_body(gs, ms, a, h, v, **body_kw)
        return (gs, ms, dropped + out["dropped"]), ()

    (gs, merge_state, dropped), _ = jax.lax.scan(
        body, (gs, merge_state, jnp.int32(0)),
        (packed_acks_seq, packed_holds_seq, packed_votes_seq))
    jax.debug.callback(_assert_no_dropped, dropped)
    merged, count, committed = recycled_committed_prefix(gs.rs, merge_state)
    return gs, merge_state, merged, count, committed
