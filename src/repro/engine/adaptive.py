"""Per-group adaptive tick batching: lagging groups absorb extra tiles.

The sharded engine ticks all G groups in lock-step — one traffic tile
per group per fused pass — so one lagging group (deep unconsumed
backlog, unstable dissemination, stalled votes) rate-limits the whole
pass: every other group burns a full merge round per tile while the
laggard crawls.  This module lets a pass absorb ``k_g ∈ {1..K}``
pre-packed tiles for lagging groups while caught-up groups absorb at
most 1 (often 0 once drained), *without changing any jitted shape* and
*without changing the merged learner output by a single bit*.

How exactness works
-------------------

The round-robin merge (:mod:`repro.engine.merge`) interleaves per-group
logs by **round**: entry (g, r) sits at round-robin position r·G + g.
Lock-step ticking appends exactly one round per group per tick, so
round r of group g always holds what group g assigned at its r-th tick.
Adaptive batching preserves precisely that invariant:

* every pass advances **all** groups by the same ``R ∈ {1..K}`` rounds
  (``R`` is chosen from the lag spread by the policy), appended as one
  wide ``[G, R·round_width]`` block — this is where the speedup comes
  from (one merge append and one dispatch amortize R rounds);
* within a pass, group g really *ticks* for round j only when it has a
  queued tile to consume (``j < k_g``) or live assignable backlog
  (stable-but-unassigned slots that a zero-tile tick would assign);
  otherwise its round j is a pure-SKIP round appended without ticking —
  bit-for-bit what a lock-step tick over a zero tile would have logged;
* each round has a **fixed width** (:func:`merge.round_entries` with
  ``round_width = cfg.max_entries``), so a group's log content depends
  only on its own tile sequence, never on what other groups absorbed.

Hence for *pre-loaded* traffic (each group's full tile sequence queued
before the run — the fused-run regime), any pacing whatsoever (any
``K``, ``threshold``, policy) consumes tile τ of group g at round τ and
the merged prefix is bit-identical to lock-step ticking, for all four
engine families.  ``tests/test_adaptive_batching.py`` pins this as a
property.

Live feeding caveat (host-driven loops): a tile enqueued *after* its
group has already advanced past that round number is consumed at a
later round than lock-step would have placed it — still a legal
deterministic merge, identical to lock-step over the shifted arrival
schedule, but not bit-identical to the original timing.  Same class of
caveat as the fused runs' position-addressed traffic rule: id-addressed
feeders should re-read ``slot_ids`` and enqueue against the live map.

Entry points
------------

* :func:`init_queue` / :func:`enqueue` / :func:`queue_from_arrays` —
  the per-group ring buffer of pre-packed traffic tiles;
* :func:`plan_rounds` — the policy: lag metric → (R, per-group k);
* :func:`adaptive_pass` (+ jitted twin) — one masked fixed-K pass;
* :func:`run_adaptive` — scan N passes fused, then the commit gate;
* :func:`subtick_pass` — the queue-less variant ``pipeline_tick`` wires
  in: one rebuilt tile set, re-absorbed (idempotent OR) for up to K
  masked inner rounds so lagging groups get extra assignment budget.

Configured through the facade::

    cfg = EngineConfig(..., adaptive=AdaptiveConfig(
        max_tiles_per_tick=4, policy="backlog"))
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..core import jaxsim
from ..core.jaxsim import admitted_mask
from ..dissem import engine as dissem_engine
from . import merge as merge_mod
from . import sharded as sharded_mod

POLICIES = ("backlog", "undecided", "unstable")


@dataclass(frozen=True)
class AdaptiveConfig:
    """Adaptive tick-batching knobs (hashable → jit-static).

    ``max_tiles_per_tick`` (K): hard cap on rounds per merged pass — the
    inner ``fori_loop`` bound, so jitted shapes never change with load.
    ``policy``: which per-group lag metric drives the round count —
    ``"backlog"`` (unconsumed queued tiles; falls back to ``"undecided"``
    in the queue-less pipeline wiring), ``"undecided"`` (admitted but
    not yet decided slots in the :class:`~repro.core.jaxsim.QuorumState`)
    or ``"unstable"`` (admitted but not dissemination-stable slots in
    the :class:`~repro.dissem.engine.DissemState`; quorum-side stability
    for ungated families).  ``threshold``: lag units per extra round —
    a pass runs ``1 + clip((max(lag) − min(lag)) // threshold, 0, K−1)``
    rounds.  ``queue_capacity``: tiles per group the
    :class:`TrafficQueue` ring holds."""
    max_tiles_per_tick: int
    policy: str = "backlog"
    threshold: int = 1
    queue_capacity: int = 64

    def __post_init__(self):
        if int(self.max_tiles_per_tick) < 1:
            raise ValueError("AdaptiveConfig.max_tiles_per_tick must be "
                             f">= 1, got {self.max_tiles_per_tick}")
        if self.policy not in POLICIES:
            raise ValueError(f"AdaptiveConfig.policy={self.policy!r} not "
                             f"in {POLICIES}")
        if int(self.threshold) < 1:
            raise ValueError("AdaptiveConfig.threshold must be >= 1, got "
                             f"{self.threshold}")
        if int(self.queue_capacity) < 1:
            raise ValueError("AdaptiveConfig.queue_capacity must be >= 1, "
                             f"got {self.queue_capacity}")


class TrafficQueue(NamedTuple):
    """Per-group ring buffer of pre-packed traffic tiles.

    ``acks``: uint32[G, C, W, WORDS_D]; ``votes``: uint32[G, C, W,
    WORDS_S]; ``holds``: uint32[G, C, W, WORDS_P] for gated families,
    ``None`` otherwise (C = ``AdaptiveConfig.queue_capacity``).  ``head``
    / ``tail`` are per-group int32 cursors (tile t lives at physical
    slot t % C); ``dropped`` counts tiles rejected by a full ring."""
    acks: jax.Array
    votes: jax.Array
    holds: Any
    head: jax.Array      # int32[G]
    tail: jax.Array      # int32[G]
    dropped: jax.Array   # int32[G]


def init_queue(cfg, capacity: int | None = None) -> TrafficQueue:
    """Empty :class:`TrafficQueue` shaped for ``cfg`` (an
    :class:`~repro.engine.api.EngineConfig` with ``adaptive`` set);
    ``capacity`` overrides ``cfg.adaptive.queue_capacity``."""
    if cfg.adaptive is None:
        raise ValueError("init_queue() needs EngineConfig.adaptive set")
    C = int(cfg.adaptive.queue_capacity if capacity is None else capacity)
    G, W = cfg.groups, cfg.window
    holds = None
    if cfg.gating is not None:
        holds = jnp.zeros(
            (G, C, W, jaxsim._words(cfg.gating.n_diss_partition)),
            jnp.uint32)
    # head/tail/dropped are three separate allocations on purpose: the
    # queue is a donated operand of adaptive_pass_jit, and donating a
    # pytree holding the same buffer in two leaves is a runtime error
    # ("attempt to donate the same buffer twice")
    return TrafficQueue(
        acks=jnp.zeros((G, C, W, jaxsim._words(cfg.n_diss)), jnp.uint32),
        votes=jnp.zeros((G, C, W, jaxsim._words(cfg.n_seq)), jnp.uint32),
        holds=holds, head=jnp.zeros((G,), jnp.int32),
        tail=jnp.zeros((G,), jnp.int32),
        dropped=jnp.zeros((G,), jnp.int32))


def backlog(queue: TrafficQueue) -> jax.Array:
    """int32[G]: unconsumed tiles per group (the ``"backlog"`` lag)."""
    return queue.tail - queue.head


def enqueue(queue: TrafficQueue, acks: jax.Array, votes: jax.Array,
            holds: jax.Array | None = None,
            mask: jax.Array | None = None) -> TrafficQueue:
    """Append one tile set per group (rows where ``mask``, default all).

    acks: uint32[G, W, WORDS_D], votes: uint32[G, W, WORDS_S], holds
    required exactly when the queue carries them.  A full ring rejects
    the tile and counts it in ``queue.dropped`` — callers should size
    ``queue_capacity`` for the worst-case burst and assert ``dropped``
    stays zero (dropping traffic is lossy, not merely slow)."""
    if (queue.holds is None) != (holds is None):
        raise ValueError(
            "hold tiles are required exactly when the queue carries them: "
            f"queue {'has' if queue.holds is not None else 'lacks'} holds, "
            f"enqueue() {'got' if holds is not None else 'missing'} them")
    G, C = queue.acks.shape[:2]
    if mask is None:
        mask = jnp.ones((G,), jnp.bool_)
    fits = (queue.tail - queue.head) < C
    write = mask & fits
    g = jnp.arange(G)
    pos = jnp.where(write, queue.tail % C, C)    # C = out of bounds → drop
    new = queue._replace(
        acks=queue.acks.at[g, pos].set(acks, mode="drop"),
        votes=queue.votes.at[g, pos].set(votes, mode="drop"),
        tail=queue.tail + write.astype(jnp.int32),
        dropped=queue.dropped + (mask & ~fits).astype(jnp.int32))
    if holds is not None:
        new = new._replace(holds=queue.holds.at[g, pos].set(holds,
                                                            mode="drop"))
    return new


def queue_from_arrays(cfg, acks_seq, votes_seq, holds_seq=None,
                      lengths=None) -> TrafficQueue:
    """Pre-loaded queue from lock-step traffic arrays.

    acks_seq: uint32[T, G, W, WORDS_D] (the exact input shape of the
    legacy ``run_*_ticks_merged`` scans), likewise votes/holds.
    ``lengths`` int[G] gives each group's true tile count (≤ T; default
    T for all) — trailing tiles past a group's length are never
    consumed, which is how a skewed workload (one slow group with T
    tiles, fast groups with fewer) is expressed.  Pre-loading is the
    regime where adaptive pacing is bit-identical to lock-step (see the
    module docstring)."""
    if (cfg.gating is not None) != (holds_seq is not None):
        raise ValueError(
            "hold traffic is required exactly when gating is configured: "
            f"family={cfg.family!r}, holds_seq "
            f"{'missing' if holds_seq is None else 'given'}")
    T = acks_seq.shape[0]
    G = acks_seq.shape[1]
    lengths = jnp.full((G,), T, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)
    return TrafficQueue(
        acks=jnp.swapaxes(jnp.asarray(acks_seq), 0, 1),
        votes=jnp.swapaxes(jnp.asarray(votes_seq), 0, 1),
        holds=None if holds_seq is None
        else jnp.swapaxes(jnp.asarray(holds_seq), 0, 1),
        head=jnp.zeros((G,), jnp.int32), tail=lengths,
        dropped=jnp.zeros((G,), jnp.int32))


# -- lag metrics --------------------------------------------------------------

def _quorum(cfg, core) -> jaxsim.QuorumState:
    """The leading-G QuorumState of any family's core state."""
    fam = cfg.family
    if fam in ("plain", "gated"):
        return core
    if fam == "recycled":
        return core.q
    return core.rs.q


def _dissem(cfg, core, dissem):
    """The DissemState of a gated family's state (None for ungated)."""
    if cfg.family == "gated":
        return dissem
    if cfg.family == "gated_recycled":
        return core.d
    return None


def undecided_depth(q: jaxsim.QuorumState) -> jax.Array:
    """int32[G]: admitted-but-undecided slots per group — the ordering-
    side lag metric (``"undecided"`` policy)."""
    return jnp.sum(admitted_mask(q) & ~q.decided, axis=-1, dtype=jnp.int32)


def _assignable(q: jaxsim.QuorumState) -> jax.Array:
    """int32[G]: stable-but-unassigned slots — what a zero-tile tick
    would still make progress on (the leader's pending order backlog)."""
    return jnp.sum(q.stable & (q.instance < 0), axis=-1, dtype=jnp.int32)


def _state_lag(cfg, core, dissem, policy: str) -> jax.Array:
    """Per-group lag from engine state alone (no queue).

    Takes the family ``core``/``dissem`` pair rather than an
    EngineState so the meshed path can evaluate it on a device's local
    group rows (the metric is row-wise; only the spread reduction in
    :func:`_rounds_from_spread` crosses groups)."""
    q = _quorum(cfg, core)
    if policy == "undecided":
        return undecided_depth(q)
    d = _dissem(cfg, core, dissem)
    if d is not None:
        return dissem_engine.unstable_backlog(d)
    # ungated families: quorum-side stability plays the dissemination role
    return jnp.sum(admitted_mask(q) & ~q.stable, axis=-1, dtype=jnp.int32)


def _rounds_from_spread(ad: AdaptiveConfig, lag: jax.Array) -> jax.Array:
    spread = jnp.max(lag) - jnp.min(lag)
    return (1 + jnp.clip(spread // ad.threshold, 0,
                         ad.max_tiles_per_tick - 1)).astype(jnp.int32)


def plan_rounds(cfg, state, queue: TrafficQueue)\
        -> tuple[jax.Array, jax.Array]:
    """The batching policy: (R scalar int32, k int32[G]).

    ``R ∈ {0..K}`` is the uniform round count of the next pass (0 iff
    every group is fully drained *and* has no assignable backlog — a
    guaranteed no-op pass); ``k = min(R, backlog)`` is how many queued
    tiles each group actually consumes.  Uniform R is what keeps the
    round-robin merge aligned (see module docstring); per-group
    adaptivity lives in k — a lagging group consumes R tiles while a
    caught-up group consumes what it has (1 in steady state, 0 once
    drained, the drained rounds appended as pure SKIP)."""
    ad = cfg.adaptive
    rem = backlog(queue)
    lag = rem if ad.policy == "backlog" \
        else _state_lag(cfg, state.core, state.dissem, ad.policy)
    R = _rounds_from_spread(ad, lag)
    need = (rem > 0) | (_assignable(_quorum(cfg, state.core)) > 0)
    R = jnp.where(jnp.any(need), R, 0).astype(jnp.int32)
    return R, jnp.minimum(R, rem).astype(jnp.int32)


# -- the masked fixed-K pass --------------------------------------------------

def _select_groups(mask: jax.Array, new, old):
    """Per-group pytree select: leaves have a leading G axis."""
    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def _family_tick(cfg, core, dissem, slot_ids, acks, votes, holds,
                 id_base=None):
    """One full engine tick of all groups, any family: absorb → assign →
    vote (→ recycle).  Returns (core', dissem', assigned int32[G, W],
    sids int32[G, W] — the slot→id map *at assignment time*, i.e. before
    any recycle, which is what merge entries must snapshot).

    Shape-polymorphic in the leading row axis; ``id_base`` is the
    recycled families' fresh-id range override (``sharded.recycle_groups``)
    — the meshed engine passes global group offsets for its local rows."""
    fam = cfg.family
    vtick = jax.vmap(functools.partial(
        jaxsim.engine_tick_packed, diss_majority=cfg.diss_majority,
        seq_majority=cfg.seq_majority, order_budget=cfg.order_budget))

    def gated_step(q, d):
        return sharded_mod._gated_step(
            q, d, acks, holds, votes, diss_majority=cfg.diss_majority,
            seq_majority=cfg.seq_majority,
            stab_majority=cfg.gating.stab_majority,
            order_budget=cfg.order_budget)

    if fam == "plain":
        q, out = vtick(core, acks, votes)
        return q, None, out["assigned"], slot_ids
    if fam == "gated":
        q, d, out = gated_step(core, dissem)
        return q, d, out["assigned"], slot_ids
    if fam == "recycled":
        q, out = vtick(core.q, acks, votes)
        sids = core.slot_ids
        rs = sharded_mod.RecycleState(q=q, slot_ids=sids,
                                      retired=core.retired)
        rs, _ = sharded_mod.recycle_groups(
            rs, watermark=cfg.recycling.watermark,
            id_stride=cfg.recycling.id_stride, id_base=id_base)
        return rs, None, out["assigned"], sids
    # gated_recycled
    q, d, out = gated_step(core.rs.q, core.d)
    sids = core.rs.slot_ids
    gs = sharded_mod.GatedRecycleState(
        rs=sharded_mod.RecycleState(q=q, slot_ids=sids,
                                    retired=core.rs.retired), d=d)
    gs, _ = sharded_mod.gated_recycle_groups(
        gs, watermark=cfg.recycling.watermark,
        id_stride=cfg.recycling.id_stride,
        fresh_stable=cfg.gating.fresh_stable, id_base=id_base)
    return gs, None, out["assigned"], sids


def _masked_rounds_core(cfg, core, dissem, slot_ids, R, tile_fn,
                        consume_of, id_base=None):
    """The fixed-K ``fori_loop`` of an adaptive pass, merge append
    excluded.

    Round j ticks exactly the groups ``consume_of(j) | assignable``
    (masked per group, whole-round compute skipped via ``lax.cond``
    when no group is active) and writes its fixed-width entries into a
    [rows, K·rw] SKIP-initialized buffer.  Shape-polymorphic in the
    leading row axis: the unmeshed wrapper runs it over all G groups,
    the meshed path over one device's local rows (the per-group cond
    gate makes local any-activity skipping bit-exact — an inactive
    group's round is all-SKIP either way).  Returns ``(core, dissem,
    buf, dropped)``."""
    K = cfg.adaptive.max_tiles_per_tick
    rw = cfg.max_entries
    rows = jax.tree.leaves(core)[0].shape[0]

    def body(j, carry):
        core, dissem, buf, dropped = carry
        consume = consume_of(j)                              # bool[rows]
        assignable = _assignable(_quorum(cfg, core)) > 0
        active = (j < R) & (consume | assignable)

        def run_round(carry):
            core, dissem, buf, dropped = carry
            a, v, h = tile_fn(j, consume)
            ncore, ndissem, assigned, sids = _family_tick(
                cfg, core, dissem, slot_ids, a, v, h, id_base=id_base)
            assigned = jnp.where(active[:, None], assigned, -1)
            entries, _, drop_g = merge_mod.round_entries(assigned, sids,
                                                         rw)
            buf = jax.lax.dynamic_update_slice(
                buf, entries, (jnp.int32(0), j * rw))
            dropped = dropped + jnp.sum(
                jnp.where(active, drop_g, 0), dtype=jnp.int32)
            core = _select_groups(active, ncore, core)
            if dissem is not None:
                dissem = _select_groups(active, ndissem, dissem)
            return core, dissem, buf, dropped

        return jax.lax.cond(jnp.any(active), run_round, lambda c: c,
                            (core, dissem, buf, dropped))

    buf = jnp.full((rows, K * rw), merge_mod.SKIP, jnp.int32)
    return jax.lax.fori_loop(0, K, body,
                             (core, dissem, buf, jnp.int32(0)))


def _masked_rounds(cfg, state, R, tile_fn, consume_of):
    """Shared inner loop of :func:`adaptive_pass` / :func:`subtick_pass`:
    run :func:`_masked_rounds_core` over all G groups, then merge-append
    R·rw entries per group in one wide write."""
    core, dissem, buf, dropped = _masked_rounds_core(
        cfg, state.core, state.dissem, state.slot_ids, R, tile_fn,
        consume_of)
    rw = cfg.max_entries
    counts = jnp.broadcast_to(R * rw, (cfg.groups,)).astype(jnp.int32)
    ms = merge_mod.append_entries(state.merge, buf, counts)
    return state._replace(core=core, dissem=dissem, merge=ms), dropped


def adaptive_pass(cfg, state, queue: TrafficQueue)\
        -> tuple[Any, TrafficQueue, dict]:
    """One adaptive merged pass: consume up to K queued tiles per group.

    Functional core (``cfg`` static under jit — use
    :func:`adaptive_pass_jit` from host loops).  Returns
    ``(state, queue, out)`` with ``out["rounds"]`` (scalar R of this
    pass, 0 = engine fully drained), ``out["consumed"]`` int32[G] tiles
    dequeued, and ``out["dropped"]`` (merge-truncation count, always 0
    given the config-time ``max_entries ≥ order_budget`` check)."""
    if cfg.adaptive is None:
        raise ValueError("adaptive_pass() needs EngineConfig.adaptive set")
    if (queue.holds is None) != (cfg.gating is None):
        raise ValueError(
            "queue hold tiles are required exactly when gating is "
            f"configured: family={cfg.family!r}")
    if cfg.mesh is not None:
        from . import meshed as meshed_mod
        return meshed_mod.adaptive_pass(cfg, state, queue)
    C = queue.acks.shape[1]
    g = jnp.arange(cfg.groups)
    R, k = plan_rounds(cfg, state, queue)

    def tile_fn(j, consume):
        slot = (queue.head + j) % C
        def take(buf):
            m = consume.reshape((-1,) + (1,) * (buf.ndim - 2))
            return jnp.where(m, buf[g, slot], jnp.uint32(0))
        holds = None if queue.holds is None else take(queue.holds)
        return take(queue.acks), take(queue.votes), holds

    state, dropped = _masked_rounds(cfg, state, R, tile_fn,
                                    lambda j: j < k)
    queue = queue._replace(head=queue.head + k)
    return state, queue, {"rounds": R, "consumed": k, "dropped": dropped}


# state and queue are donated: one adaptive pass rewrites both wholesale,
# so the input trees are dead the moment the call returns (callers thread
# the returned pair; anyone re-reading the donated inputs gets jax's
# deleted-buffer error, not silent stale data)
adaptive_pass_jit = jax.jit(adaptive_pass, static_argnames=("cfg",),
                            donate_argnums=(1, 2))


@functools.partial(jax.jit, static_argnames=("cfg", "n_passes"),
                   donate_argnums=(1, 2))
def run_adaptive(cfg, state, queue: TrafficQueue, *, n_passes: int)\
        -> tuple[Any, TrafficQueue, jax.Array, jax.Array, jax.Array]:
    """Fused adaptive hot loop: scan ``n_passes`` passes, then gate.

    The adaptive twin of ``api.run`` — same return contract
    ``(state, merged, merged_count, committed_count)`` with the queue
    threaded through: returns ``(state, queue, merged, count,
    committed)``.  Passes beyond the drain point are guaranteed no-ops
    (R = 0: nothing ticks, nothing appends), so ``n_passes`` only needs
    to be an upper bound — ``ceil(max_tiles / K) + catch-up slack`` —
    and overshooting is cheap.  Position-addressed traffic caveat as
    the legacy fused runs: tiles index slots by position and recycling
    remaps mid-scan, so only position-uniform traffic is sound here."""
    def body(carry, _):
        st, q = carry
        st, q, out = adaptive_pass(cfg, st, q)
        return (st, q), (out["rounds"], out["dropped"])

    (state, queue), (rounds, dropped) = jax.lax.scan(
        body, (state, queue), None, length=n_passes)
    jax.debug.callback(sharded_mod._assert_no_dropped, jnp.sum(dropped))
    from . import api as api_mod   # runtime import: api imports this module
    merged, count, committed = api_mod.committed_prefix(cfg, state)
    return state, queue, merged, count, committed


def subtick_pass(cfg, state, acks: jax.Array, votes: jax.Array,
                 holds: jax.Array | None = None) -> tuple[Any, dict]:
    """The queue-less pipeline wiring: one tile set, up to K rounds.

    ``pipeline.closed.pipeline_tick`` rebuilds monotone age-based tiles
    from the live slot map every tick, so there is nothing to queue —
    instead, when lag has spread across groups, the same tiles are
    re-absorbed (idempotent OR, a no-op on the bitsets) for up to K−1
    extra *assignment* rounds: a lagging group's stable backlog drains
    at ``R × order_budget`` ids per pipeline tick instead of
    ``order_budget``, while caught-up groups pad pure-SKIP rounds.  The
    ``"backlog"`` policy resolves to ``"undecided"`` here (no queue to
    measure).  Every group always ticks round 0 — with R = 1 this is
    exactly the lock-step facade tick, fixed round width aside.
    Returns ``(state, out)`` like ``api.tick`` (plus ``out["rounds"]``)."""
    if cfg.adaptive is None:
        raise ValueError("subtick_pass() needs EngineConfig.adaptive set")
    if cfg.mesh is not None:
        from . import meshed as meshed_mod
        return meshed_mod.subtick_pass(cfg, state, acks, votes, holds)
    policy = "undecided" if cfg.adaptive.policy == "backlog" \
        else cfg.adaptive.policy
    R = _rounds_from_spread(
        cfg.adaptive, _state_lag(cfg, state.core, state.dissem, policy))

    def tile_fn(j, consume):
        return acks, votes, holds

    def consume_of(j):
        return jnp.full((cfg.groups,), j == 0)

    state, dropped = _masked_rounds(cfg, state, R, tile_fn, consume_of)
    return state, {"rounds": R, "dropped": dropped}
