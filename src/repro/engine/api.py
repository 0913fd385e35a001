"""Unified Engine facade over the four sharded-engine families.

``repro.engine`` grew four parallel function families — ``plain``
(single-use window), ``recycled`` (sustained window, watermark-gated
compaction), ``gated`` (dissemination-stability gate on phase-2b votes)
and ``gated_recycled`` (both) — each with its own ``init_*`` /
``*_tick*`` / ``run_*_ticks_merged`` / ``recycle_*`` / ``reconfigure_*``
spelling and its own keyword conventions (``watermark``, ``id_stride``,
``max_entries``, ``fresh_stable``, ...). This module collapses them
behind one configuration object and one facade:

    cfg = EngineConfig(groups=4, window=256, n_diss=5, n_seq=3,
                       order_budget=8, merge_capacity=4096,
                       recycling=RecyclingConfig(watermark=64,
                                                 id_stride=1 << 20),
                       gating=GatingConfig())
    eng = Engine.create(cfg)
    out = eng.tick(acks, votes, holds)      # one step, merge-appended
    merged, count, committed = eng.run(acks_seq, votes_seq, holds_seq)

Every knob is normalized and validated **once**, at config construction
(``EngineConfig.__post_init__``) — majorities default to ``n // 2 + 1``,
``max_entries`` resolves against ``order_budget`` exactly as the legacy
``_resolve_max_entries`` did, and the recycled families' ``id_stride``
rule (explicit stride required for ``groups > 1``) fails fast instead of
at first recycle. The facade methods then *delegate* to the legacy
functions, so every config cell is bit-identical to the family it wraps
(pinned by ``tests/test_engine_api.py``).

Two layers, both public:

* **functional** — ``create_state`` / ``tick`` / ``run`` / ``recycle`` /
  ``reconfigure`` / ``committed_prefix`` over an :class:`EngineState`
  pytree, with the (hashable) :class:`EngineConfig` passed as a static
  argument: this is what jit-compiled callers close over
  (``repro.pipeline`` scans ``tick`` inside one fused computation);
* **object** — :class:`Engine`, a thin stateful wrapper for host-driven
  loops and interactive use.

The legacy names remain importable from their defining modules
(``repro.engine.sharded`` / ``repro.engine.epochs``) without warnings;
package-level access (``repro.engine.init_recycled``) emits
``DeprecationWarning`` — see ``repro/engine/__init__.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..dissem.engine import DissemState, init_dissem
from . import adaptive as adaptive_mod
from . import epochs as epochs_mod
from . import merge as merge_mod
from . import sharded as sharded_mod
from . import stages
from .adaptive import AdaptiveConfig
from .epochs import EpochTable


@dataclass(frozen=True)
class RecyclingConfig:
    """Window-recycling knobs (the ``recycled_*`` family).

    ``watermark``: a group compacts when its free-slot count drops below
    this. ``id_stride``: width of each group's private id range; must be
    explicit for ``groups > 1`` (fresh ids are issued past
    ``g·id_stride + window`` and are never range-checked on the jit
    path); ``None`` is only legal for a single group, where it resolves
    to ``window``."""
    watermark: int
    id_stride: int | None = None


@dataclass(frozen=True)
class GatingConfig:
    """Dissemination-stability gating knobs (the ``gated_*`` family).

    ``n_diss_partition``: per-group disseminator partition size (m/G;
    ``None`` → ``n_diss``, the global set). ``stab_majority``: holds
    needed for stability (``None`` → majority of the partition).
    ``pre_stable`` seeds every slot already-stable (the ungated
    bit-identity baseline); ``fresh_stable`` is what recycled slots are
    reborn with."""
    stab_majority: int | None = None
    n_diss_partition: int | None = None
    pre_stable: bool = False
    fresh_stable: bool = False


@dataclass(frozen=True)
class MeshConfig:
    """Device-sharded group execution knobs (``repro.engine.meshed``).

    When set on :class:`EngineConfig`, the hot entry points
    (:func:`tick`, :func:`run`, ``adaptive_pass`` and the pipeline's
    engine stage) partition the G group rows across a 1-D ``("group",)``
    device mesh with ``shard_map``: per-group quorum/stability/adaptive
    work runs device-parallel with zero cross-device traffic, and only
    the round-robin merge crosses devices (one ``all_gather`` of
    fixed-width entry rows per pass). The merged learner log is
    **bit-identical** to the unmeshed path for any device count.

    ``n_devices``: mesh size; ``None`` → all available devices. Clamped
    at first use to the available device count and to ``groups`` via
    ``launch.mesh.make_group_mesh`` (when the clamped size does not
    divide ``groups``, inert pad rows are added internally and sliced
    off before the merge). ``axis_name``: the mesh axis name."""
    n_devices: int | None = None
    axis_name: str = "group"


def _majority(n: int) -> int:
    return n // 2 + 1


@dataclass(frozen=True)
class EngineConfig:
    """Single source of truth for one engine instance.

    Construction normalizes every defaultable field in place (the frozen
    instance you hold has no ``None`` left in ``diss_majority`` /
    ``seq_majority`` / ``max_entries`` / ``recycling.id_stride`` /
    ``gating.*``) and raises ``ValueError`` on any inconsistency — the
    checks the legacy families deferred to first use
    (``_resolve_max_entries``, ``init_recycled``'s stride rule) happen
    here, before any array is allocated. Hashable, so jitted callers can
    pass it as a static argument."""
    groups: int
    window: int
    n_diss: int
    n_seq: int
    order_budget: int
    merge_capacity: int
    diss_majority: int | None = None
    seq_majority: int | None = None
    max_entries: int | None = None
    recycling: RecyclingConfig | None = None
    gating: GatingConfig | None = None
    epochs: EpochTable | None = None
    adaptive: AdaptiveConfig | None = None
    mesh: MeshConfig | None = None

    def __post_init__(self):
        def norm(field, value):
            object.__setattr__(self, field, value)

        for f in ("groups", "window", "n_diss", "n_seq", "order_budget",
                  "merge_capacity"):
            if int(getattr(self, f)) < 1:
                raise ValueError(f"EngineConfig.{f} must be >= 1, got "
                                 f"{getattr(self, f)}")
            norm(f, int(getattr(self, f)))
        if self.diss_majority is None:
            norm("diss_majority", _majority(self.n_diss))
        if self.seq_majority is None:
            norm("seq_majority", _majority(self.n_seq))
        for f, n in (("diss_majority", self.n_diss),
                     ("seq_majority", self.n_seq)):
            v = int(getattr(self, f))
            if not 1 <= v <= n:
                raise ValueError(f"EngineConfig.{f}={v} out of range "
                                 f"[1, {n}]")
            norm(f, v)
        # merge-buffer width: the legacy _resolve_max_entries contract,
        # enforced at config time so no tick can ever silently truncate
        if self.max_entries is None:
            norm("max_entries", self.order_budget)
        elif int(self.max_entries) < self.order_budget:
            raise ValueError(
                f"max_entries={self.max_entries} < order_budget="
                f"{self.order_budget}: a tick could assign more ids than "
                "the merge buffer holds — truncated entries desynchronize "
                "the commit gate's instance ranks")
        else:
            norm("max_entries", int(self.max_entries))
        if self.recycling is not None:
            r = self.recycling
            if int(r.watermark) < 1:
                raise ValueError(
                    f"RecyclingConfig.watermark must be >= 1, got "
                    f"{r.watermark}")
            if r.id_stride is None:
                if self.groups > 1:
                    raise ValueError(
                        "RecyclingConfig.id_stride must be explicit for "
                        "groups > 1: recycling issues fresh ids past "
                        "g*id_stride + window, so a defaulted stride of "
                        "`window` would collide with the next group's id "
                        "range at the first recycle")
                r = RecyclingConfig(int(r.watermark), self.window)
            elif int(r.id_stride) < self.window:
                raise ValueError(
                    f"RecyclingConfig.id_stride={r.id_stride} < window="
                    f"{self.window}: a group's initial window would "
                    "already overlap the next group's id range")
            else:
                r = RecyclingConfig(int(r.watermark), int(r.id_stride))
            norm("recycling", r)
        if self.gating is not None:
            g = self.gating
            part = self.n_diss if g.n_diss_partition is None \
                else int(g.n_diss_partition)
            if part < 1:
                raise ValueError(
                    f"GatingConfig.n_diss_partition must be >= 1, got "
                    f"{g.n_diss_partition}")
            stab = _majority(part) if g.stab_majority is None \
                else int(g.stab_majority)
            if not 1 <= stab <= part:
                raise ValueError(
                    f"GatingConfig.stab_majority={stab} out of range "
                    f"[1, {part}]")
            norm("gating", GatingConfig(stab, part, bool(g.pre_stable),
                                        bool(g.fresh_stable)))
        if self.adaptive is not None and \
                not isinstance(self.adaptive, AdaptiveConfig):
            raise ValueError(
                f"EngineConfig.adaptive must be an AdaptiveConfig, got "
                f"{type(self.adaptive).__name__}")
        if self.mesh is not None:
            m = self.mesh
            if not isinstance(m, MeshConfig):
                raise ValueError(
                    f"EngineConfig.mesh must be a MeshConfig, got "
                    f"{type(m).__name__}")
            if m.n_devices is not None and int(m.n_devices) < 1:
                raise ValueError(
                    f"MeshConfig.n_devices must be >= 1, got "
                    f"{m.n_devices}")
            norm("mesh", MeshConfig(
                None if m.n_devices is None else int(m.n_devices),
                str(m.axis_name)))
        if self.epochs is not None and self.epochs.n_rows != self.groups:
            raise ValueError(
                f"EpochTable.n_rows={self.epochs.n_rows} must equal "
                f"groups={self.groups}: physical rows are allocated once "
                "and epochs activate subsets")

    @property
    def family(self) -> str:
        """Which legacy function family this config resolves to."""
        if self.recycling is not None:
            return "gated_recycled" if self.gating is not None \
                else "recycled"
        return "gated" if self.gating is not None else "plain"


class EngineState(NamedTuple):
    """The facade's engine state pytree.

    ``core`` is the family state exactly as the legacy functions define
    it (QuorumState / RecycleState / GatedRecycleState); ``dissem`` is
    the DissemState of the non-recycled gated family (``None``
    otherwise — recycled gating carries it inside GatedRecycleState);
    ``slot_ids`` is the slot→id map of the non-recycled families
    (``None`` otherwise — it lives in RecycleState). ``merge`` is the
    deterministic merge log."""
    core: Any
    dissem: Any
    slot_ids: Any
    merge: merge_mod.MergeState


def create_state(cfg: EngineConfig) -> EngineState:
    """Fresh engine state for a validated config."""
    ms = merge_mod.init_merge(cfg.groups, cfg.merge_capacity)
    if cfg.family == "plain":
        return EngineState(
            core=sharded_mod.init_sharded(cfg.groups, cfg.window,
                                          cfg.n_diss, cfg.n_seq),
            dissem=None,
            slot_ids=sharded_mod.default_slot_ids(cfg.groups, cfg.window),
            merge=ms)
    if cfg.family == "gated":
        return EngineState(
            core=sharded_mod.init_sharded(cfg.groups, cfg.window,
                                          cfg.n_diss, cfg.n_seq),
            dissem=init_dissem(cfg.groups, cfg.window,
                               cfg.gating.n_diss_partition,
                               pre_stable=cfg.gating.pre_stable),
            slot_ids=sharded_mod.default_slot_ids(cfg.groups, cfg.window),
            merge=ms)
    if cfg.family == "recycled":
        return EngineState(
            core=sharded_mod.init_recycled(
                cfg.groups, cfg.window, cfg.n_diss, cfg.n_seq,
                id_stride=cfg.recycling.id_stride),
            dissem=None, slot_ids=None, merge=ms)
    return EngineState(
        core=sharded_mod.init_gated_recycled(
            cfg.groups, cfg.window, cfg.n_diss, cfg.n_seq,
            n_diss_partition=cfg.gating.n_diss_partition,
            id_stride=cfg.recycling.id_stride,
            pre_stable=cfg.gating.pre_stable),
        dissem=None, slot_ids=None, merge=ms)


def slot_ids(state: EngineState) -> jax.Array:
    """Live slot→global-id map, whichever family holds it."""
    if state.slot_ids is not None:
        return state.slot_ids
    core = state.core
    if isinstance(core, sharded_mod.GatedRecycleState):
        return core.rs.slot_ids
    return core.slot_ids


def progress(cfg: EngineConfig, state: EngineState) -> dict:
    """Running totals over all groups, int32 scalars: ``ordered``
    (instances assigned), ``decided`` (ids decided on the commit quorum)
    and, for gated families, ``stable`` (ids stable). A retired id counts
    as decided and stable: only decided ids retire, and a decided id
    passed the stability gate. The difference of two readings counts
    what the steps between them did, on every path that keeps this state
    (facade, adaptive, meshed). On a meshed config the state is sharded by
    group, so each sum reduces across the mesh."""
    if cfg.recycling is not None:
        rs = state.core.rs if cfg.family == "gated_recycled" \
            else state.core
        q, retired = rs.q, rs.retired.sum(dtype=jnp.int32)
    else:
        q, retired = state.core, jnp.int32(0)
    out = {"ordered": q.next_instance.sum(dtype=jnp.int32),
           "decided": q.decided.sum(dtype=jnp.int32) + retired}
    if cfg.gating is not None:
        d = state.core.d if cfg.family == "gated_recycled" \
            else state.dissem
        out["stable"] = d.stable.sum(dtype=jnp.int32) + retired
    return out


def _need_holds(cfg: EngineConfig, holds) -> None:
    if (cfg.gating is not None) == (holds is None):
        raise ValueError(
            "hold tiles are required exactly when gating is configured: "
            f"family={cfg.family!r}, holds "
            f"{'missing' if holds is None else 'given'}")


def tick(cfg: EngineConfig, state: EngineState, acks: jax.Array,
         votes: jax.Array, holds: jax.Array | None = None)\
        -> tuple[EngineState, dict]:
    """One merge-appended engine step (recycled families also recycle).

    Trace-safe with ``cfg`` static; the host-driven single-step entry
    point for id-addressed traffic (re-read :func:`slot_ids` between
    calls — recycling remaps slots). Returns ``(state, out)`` with the
    family tick's outputs plus ``out["dropped"]`` (always 0 given the
    config-time ``max_entries`` check; returned so run loops can assert
    it).

    With ``cfg.mesh`` set, dispatches to the device-sharded path
    (``engine.meshed``): same state pytree and merge log bit-for-bit,
    but ``out`` is the reduced meshed dict (``assigned``/``dropped``)."""
    _need_holds(cfg, holds)
    if cfg.mesh is not None:
        from . import meshed as meshed_mod
        return meshed_mod.tick(cfg, state, acks, votes, holds)
    fam = cfg.family
    if fam == "recycled":
        rs, ms, out = sharded_mod.recycled_tick_merged(
            state.core, state.merge, acks, votes,
            diss_majority=cfg.diss_majority, seq_majority=cfg.seq_majority,
            order_budget=cfg.order_budget, max_entries=cfg.max_entries,
            watermark=cfg.recycling.watermark,
            id_stride=cfg.recycling.id_stride)
        return state._replace(core=rs, merge=ms), out
    if fam == "gated_recycled":
        gs, ms, out = sharded_mod.gated_recycled_tick_merged(
            state.core, state.merge, acks, holds, votes,
            diss_majority=cfg.diss_majority, seq_majority=cfg.seq_majority,
            stab_majority=cfg.gating.stab_majority,
            order_budget=cfg.order_budget, max_entries=cfg.max_entries,
            watermark=cfg.recycling.watermark,
            id_stride=cfg.recycling.id_stride,
            fresh_stable=cfg.gating.fresh_stable)
        return state._replace(core=gs, merge=ms), out
    if fam == "gated":
        core, d, out = sharded_mod.gated_tick(
            state.core, state.dissem, acks, holds, votes,
            diss_majority=cfg.diss_majority, seq_majority=cfg.seq_majority,
            stab_majority=cfg.gating.stab_majority,
            order_budget=cfg.order_budget)
    else:
        core, out = sharded_mod.sharded_tick(
            state.core, acks, votes, diss_majority=cfg.diss_majority,
            seq_majority=cfg.seq_majority, order_budget=cfg.order_budget)
        d = None
    entries, counts, dropped = merge_mod.entries_from_assigned(
        out["assigned"], state.slot_ids, cfg.max_entries)
    ms = merge_mod.append_entries(state.merge, entries, counts)
    return (state._replace(core=core, dissem=d, merge=ms),
            dict(out, dropped=dropped))


def run(cfg: EngineConfig, state: EngineState, acks_seq: jax.Array,
        votes_seq: jax.Array, holds_seq: jax.Array | None = None)\
        -> tuple[EngineState, jax.Array, jax.Array, jax.Array]:
    """Fused multi-tick hot loop: delegate to the family's legacy
    ``run_*_ticks_merged`` scan (bit-identical by construction). Returns
    ``(state, merged, merged_count, committed_count)`` — same contract
    and traffic-addressing caveats as the legacy functions (recycled
    families need position-uniform traffic inside a fused run).

    With ``cfg.mesh`` set, delegates to the device-sharded scan
    (``engine.meshed.run_jit``, donating) — bit-identical merged output
    for any device count."""
    _need_holds(cfg, holds_seq)
    if cfg.mesh is not None:
        from . import meshed as meshed_mod
        return meshed_mod.run_jit(cfg, state, acks_seq, votes_seq,
                                  holds_seq)
    fam = cfg.family
    kw = dict(diss_majority=cfg.diss_majority,
              seq_majority=cfg.seq_majority,
              order_budget=cfg.order_budget, max_entries=cfg.max_entries)
    if fam == "plain":
        core, ms, merged, count, committed = \
            sharded_mod.run_sharded_ticks_merged(
                state.core, state.merge, acks_seq, votes_seq,
                state.slot_ids, **kw)
        return (state._replace(core=core, merge=ms), merged, count,
                committed)
    if fam == "gated":
        core, d, ms, merged, count, committed = \
            sharded_mod.run_gated_ticks_merged(
                state.core, state.dissem, state.merge, acks_seq,
                holds_seq, votes_seq, state.slot_ids,
                stab_majority=cfg.gating.stab_majority, **kw)
        return (state._replace(core=core, dissem=d, merge=ms), merged,
                count, committed)
    kw.update(watermark=cfg.recycling.watermark,
              id_stride=cfg.recycling.id_stride)
    if fam == "recycled":
        core, ms, merged, count, committed = \
            sharded_mod.run_recycled_ticks_merged(
                state.core, state.merge, acks_seq, votes_seq, **kw)
    else:
        core, ms, merged, count, committed = \
            sharded_mod.run_gated_recycled_ticks_merged(
                state.core, state.merge, acks_seq, holds_seq, votes_seq,
                stab_majority=cfg.gating.stab_majority,
                fresh_stable=cfg.gating.fresh_stable, **kw)
    return state._replace(core=core, merge=ms), merged, count, committed


def recycle(cfg: EngineConfig, state: EngineState)\
        -> tuple[EngineState, jax.Array]:
    """Explicit watermark-gated compaction pass (normally implicit in
    :func:`tick`/:func:`run` for recycled families). Returns
    ``(state, n_retired int32[G])``."""
    if cfg.recycling is None:
        raise ValueError(
            f"recycle() needs recycling configured (family={cfg.family!r}"
            " has a single-use window)")
    if cfg.family == "gated_recycled":
        core, n = sharded_mod.gated_recycle_groups(
            state.core, watermark=cfg.recycling.watermark,
            id_stride=cfg.recycling.id_stride,
            fresh_stable=cfg.gating.fresh_stable)
    else:
        core, n = sharded_mod.recycle_groups(
            state.core, watermark=cfg.recycling.watermark,
            id_stride=cfg.recycling.id_stride)
    return state._replace(core=core), n


def reconfigure(cfg: EngineConfig, state: EngineState, old_epoch: int,
                new_epoch: int) -> tuple[EngineState, dict]:
    """Drain-then-switch epoch change (host-side control plane, between
    jitted segments). Requires ``cfg.epochs``; dispatches to the
    family's legacy ``reconfigure_*``. Returns ``(state, report)``."""
    if cfg.epochs is None:
        raise ValueError("reconfigure() needs EngineConfig.epochs set")
    fam = cfg.family
    if fam == "plain":
        core, sids, ms, report = epochs_mod.reconfigure_plain(
            state.core, state.slot_ids, state.merge, cfg.epochs,
            old_epoch, new_epoch)
        return state._replace(core=core, slot_ids=sids, merge=ms), report
    if fam == "recycled":
        core, ms, report = epochs_mod.reconfigure_recycled(
            state.core, state.merge, cfg.epochs, old_epoch, new_epoch,
            id_stride=cfg.recycling.id_stride)
        return state._replace(core=core, merge=ms), report
    if fam == "gated_recycled":
        core, ms, report = epochs_mod.reconfigure_gated_recycled(
            state.core, state.merge, cfg.epochs, old_epoch, new_epoch,
            id_stride=cfg.recycling.id_stride,
            fresh_stable=cfg.gating.fresh_stable)
        return state._replace(core=core, merge=ms), report
    raise ValueError(
        "reconfigure() is not defined for the gated non-recycled family "
        "(no legacy reconfigure_* exists: sealing removed rows needs the "
        "recycled retired-base commit gate) — add recycling")


def committed_prefix(cfg: EngineConfig, state: EngineState)\
        -> tuple[jax.Array, jax.Array, jax.Array]:
    """(merged, merged_count, committed_count) of the current state,
    without ticking — the recycle-aware commit gate for recycled
    families, the live-window gate otherwise."""
    with jax.named_scope(stages.COMMIT_GATE):
        if cfg.recycling is not None:
            rs = state.core.rs if cfg.family == "gated_recycled" \
                else state.core
            return sharded_mod.recycled_committed_prefix(rs, state.merge)
        merged, count = merge_mod.merged_prefix(state.merge)
        dec = sharded_mod._decided_by_instance(
            state.core.instance, state.core.decided,
            state.merge.logs.shape[1])
        committed = merge_mod.committed_prefix_len(state.merge, dec)
        return merged, count, committed


@functools.partial(jax.jit, static_argnames=("cfg",))
def _tick_jit(cfg, state, acks, votes, holds):
    return tick(cfg, state, acks, votes, holds)


class Engine:
    """Stateful facade: one engine instance, any family.

    ``Engine.create(cfg)`` builds fresh state; ``.tick()`` / ``.run()``
    advance it in place and return the outputs; ``.recycle()`` /
    ``.reconfigure()`` are the explicit control-plane entry points. The
    functional layer (:func:`tick` etc.) is the same machinery without
    the mutation — use it inside jit/scan."""

    def __init__(self, cfg: EngineConfig, state: EngineState,
                 epoch: int = 0) -> None:
        self.cfg = cfg
        self.state = state
        self.epoch = int(epoch)
        self.queue: adaptive_mod.TrafficQueue | None = None

    @classmethod
    def create(cls, cfg: EngineConfig, *, epoch: int = 0) -> "Engine":
        """Build a fresh engine for ``cfg`` (family implied by which
        sub-configs are present). ``epoch`` must index ``cfg.epochs``
        when an :class:`EpochTable` is configured."""
        if cfg.epochs is not None and \
                not 0 <= int(epoch) < cfg.epochs.n_epochs:
            raise ValueError(f"epoch {epoch} not in EpochTable "
                             f"(n={cfg.epochs.n_epochs})")
        return cls(cfg, create_state(cfg), epoch=epoch)

    def tick(self, acks, votes, holds=None) -> dict:
        """One engine step on pre-packed tiles — ``acks``
        uint32[G, W, WORDS_diss], ``votes`` uint32[G, W, WORDS_seq],
        ``holds`` uint32[G, W, WORDS_part] iff ``cfg.gating`` is set.
        Recycled families also compact below the watermark; re-read
        :attr:`slot_ids` afterwards (recycling remaps slots). Returns
        the family tick's outputs (``assigned``, ``dropped``, ...)."""
        self.state, out = _tick_jit(self.cfg, self.state, acks, votes,
                                    holds)
        return out

    def run(self, acks_seq, votes_seq, holds_seq=None)\
            -> tuple[jax.Array, jax.Array, jax.Array]:
        """Scan-fused multi-tick run over [T, G, W, WORDS] tile
        sequences → ``(merged, merged_count, committed_count)``.
        Recycled families need position-uniform traffic inside a fused
        run (id-addressed host loops must use :meth:`tick`)."""
        self.state, merged, count, committed = run(
            self.cfg, self.state, acks_seq, votes_seq, holds_seq)
        return merged, count, committed

    def recycle(self) -> jax.Array:
        """Explicit watermark-gated compaction (recycled families):
        retire each group's contiguous decided prefix, refill the tail
        with fresh monotone ids. Returns retired-per-group int32[G]."""
        self.state, n = recycle(self.cfg, self.state)
        return n

    def reconfigure(self, new_epoch: int) -> dict:
        """Drain-then-switch to ``new_epoch`` (requires ``cfg.epochs``).
        Precondition: rows leaving the active set are drained
        (``ValueError`` otherwise). Appends one aligned RECONFIG marker
        round, seals removed rows, re-homes in-flight ids. Returns the
        move report."""
        self.state, report = reconfigure(self.cfg, self.state,
                                         self.epoch, int(new_epoch))
        self.epoch = int(new_epoch)
        return report

    def committed(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """``(merged, merged_count, committed_count)`` for the current
        state — ``merged[:committed_count]`` is the executable prefix
        (phase-2b quorum reached; recycle-aware via retired bases)."""
        return committed_prefix(self.cfg, self.state)

    # -- adaptive tick batching (cfg.adaptive) -------------------------------

    def enqueue(self, acks, votes, holds=None, mask=None) -> None:
        """Queue one pre-packed tile set per group for adaptive passes
        (requires ``cfg.adaptive``; the queue is created lazily)."""
        if self.cfg.adaptive is None:
            raise ValueError("enqueue() needs EngineConfig.adaptive set")
        if self.queue is None:
            self.queue = adaptive_mod.init_queue(self.cfg)
        self.queue = adaptive_mod.enqueue(self.queue, acks, votes,
                                          holds=holds, mask=mask)

    def adaptive_pass(self) -> dict:
        """One adaptive merged pass over the queued traffic: lagging
        groups consume up to ``cfg.adaptive.max_tiles_per_tick`` tiles,
        caught-up groups one (or none, padded with SKIP rounds).
        Returns the pass summary (``rounds``/``consumed``/``dropped``);
        ``rounds == 0`` means the engine is fully drained."""
        if self.cfg.adaptive is None:
            raise ValueError(
                "adaptive_pass() needs EngineConfig.adaptive set")
        if self.queue is None:
            self.queue = adaptive_mod.init_queue(self.cfg)
        self.state, self.queue, out = adaptive_mod.adaptive_pass_jit(
            self.cfg, self.state, self.queue)
        return out

    @property
    def slot_ids(self) -> jax.Array:
        """Live slot→id map int32[G, W] (mutable under recycling —
        re-read between host-driven ticks)."""
        return slot_ids(self.state)

    @property
    def merge_state(self) -> merge_mod.MergeState:
        """The round-robin merge logs (``merge.MergeState``)."""
        return self.state.merge

    def __repr__(self) -> str:
        return (f"Engine(family={self.cfg.family!r}, "
                f"groups={self.cfg.groups}, window={self.cfg.window}, "
                f"epoch={self.epoch})")
