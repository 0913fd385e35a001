"""Vectorized sharded dissemination & stability engine.

HT-Paxos decouples *dissemination* (bulk payload replication across the
disseminator set + stability acknowledgements, §4.1 steps 13–20) from
*ordering* (classical Paxos on ids). ``repro.engine`` vectorizes the
ordering half; this module is the dissemination half, in the same
packed-bitset idiom: a window of W in-flight batch_ids per ordering
group, each with a ``uint32[WORDS_D]`` *hold* bitset recording which
disseminators of the group's partition hold the batch payload. An id is
**stable** — eligible for ordering — once a majority of its partition's
disseminators hold its batch (the paper's step-36 precondition: a
sequencer only counts id-multicasts, and a disseminator only
id-multicasts once it holds the batch).

Partitioned disseminator sets (§5.5's second scaling axis): with G
ordering groups, the m disseminators are split into G partitions of m/G;
a batch is replicated only within its owning group's partition, so the
per-node incoming replication bandwidth drops by ~G (see
``repro.dissem.bandwidth`` and the Figs 4–7 closed forms in
``repro.core.analytical.bytes_ht_disseminator_partitioned``). The
stability majority is then a majority *of the partition*.

Everything is a pure function over a :class:`DissemState` pytree with a
leading group axis — jit/vmap/scan-safe, mirroring
``repro.core.jaxsim``. ``repro.kernels.dissem.stability_update_grouped``
is the fused Pallas kernel for the absorb/stabilize pass
(``stability_tick_fused``); the jnp path here is its reference
implementation and the CPU/dry-run default. The ordering engine's
stability gate (``repro.engine.sharded.gated_*``) threads this state so
a slot's phase-2b votes only absorb once its id is stable.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.jaxsim import _words, pack_tile, popcount_rows


class DissemState(NamedTuple):
    """Per-group dissemination window: who holds each in-flight batch.

    Slot (g, w) tracks the same id as the ordering engine's slot (g, w)
    when the two are run side by side (the gated engine keeps them in
    lockstep, including under window recycling)."""
    hold_bits: jax.Array   # uint32[G, W, WORDS_D] — disseminators holding
    stable: jax.Array      # bool[G, W] — majority of the partition holds


def init_dissem(groups: int, window: int, n_diss: int,
                *, pre_stable: bool = False) -> DissemState:
    """Fresh dissemination window. ``n_diss`` is the *partition* size
    (disseminators per group — m/G under partitioning, m when global).
    ``pre_stable=True`` marks every slot already-stable, which makes the
    gated ordering engine bit-identical to the ungated one (the
    regression baseline)."""
    return DissemState(
        hold_bits=jnp.zeros((groups, window, _words(n_diss)), jnp.uint32),
        stable=jnp.full((groups, window), pre_stable, jnp.bool_),
    )


def absorb_holds_packed(state: DissemState, packed: jax.Array,
                        majority: int) -> tuple[DissemState, dict]:
    """OR a packed hold-tile into the window and refresh stability.

    packed: uint32[G, W, WORDS_D] (one bit per (slot, disseminator) batch
    delivery observed this tick). Returns (state, out) with
    out["counts"] int32[G, W] holder counts and out["newly_stable"]
    bool[G, W] — ids crossing the majority threshold this call."""
    hold_bits = state.hold_bits | packed
    counts = popcount_rows(hold_bits)
    stable = state.stable | (counts >= majority)
    newly = stable & ~state.stable
    return (DissemState(hold_bits=hold_bits, stable=stable),
            {"counts": counts, "newly_stable": newly})


@functools.partial(jax.jit, static_argnames=("majority",))
def stability_tick(state: DissemState, packed: jax.Array, *,
                   majority: int) -> tuple[DissemState, dict]:
    """One jitted absorb/stabilize pass (jnp reference path)."""
    return absorb_holds_packed(state, packed, majority)


@functools.partial(jax.jit, static_argnames=("majority",))
def stability_tick_dense(state: DissemState, holds: jax.Array, *,
                         majority: int) -> tuple[DissemState, dict]:
    """Bool-tile convenience wrapper: holds bool[G, W, D]."""
    return absorb_holds_packed(state, jax.vmap(pack_tile)(holds), majority)


@functools.partial(jax.jit,
                   static_argnames=("majority", "block_w", "interpret"))
def stability_tick_fused(state: DissemState, packed: jax.Array, *,
                         majority: int, block_w: int = 256,
                         interpret: bool = False)\
        -> tuple[DissemState, dict]:
    """Same pass through the fused Pallas kernel
    (``repro.kernels.dissem``): one 2-D-grid launch absorbs every group
    and also reduces the per-group newly-stable count on-chip.
    ``interpret=True`` runs the kernel body in Python (the CPU test
    path)."""
    from ..kernels.dissem import stability_update_grouped
    bits, counts, stable, newly = stability_update_grouped(
        state.hold_bits, packed, state.stable, majority=majority,
        block_w=block_w, interpret=interpret)
    return (DissemState(hold_bits=bits, stable=stable),
            {"counts": counts, "newly_stable": stable & ~state.stable,
             "newly_per_group": newly})


def run_stability_ticks(state: DissemState, packed_seq: jax.Array, *,
                        majority: int) -> tuple[DissemState, dict]:
    """lax.scan over T ticks of uint32[T, G, W, WORDS_D] hold traffic.
    The stacked out["newly_stable"] bool[T, G, W] is the stability
    *schedule* — which tick each id became orderable — consumed by the
    DES cross-validation and the bandwidth accounting."""
    def body(st, packed):
        return absorb_holds_packed(st, packed, majority)
    return jax.lax.scan(body, state, packed_seq)


def unpack_tile(packed: jax.Array, n: int) -> jax.Array:
    """uint32[..., WORDS] → bool[..., n] (inverse of jaxsim.pack_tile):
    per-disseminator hold flags, for bandwidth accounting that needs
    per-*node* rather than per-slot reductions."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(*packed.shape[:-1], packed.shape[-1] * 32)
    return flat[..., :n].astype(jnp.bool_)


def stable_ids(state: DissemState, slot_ids: jax.Array) -> jax.Array:
    """Global ids of currently-stable slots: int32[G, W] with -1 at
    unstable slots (fixed shape; callers filter host-side)."""
    return jnp.where(state.stable, slot_ids.astype(jnp.int32), -1)


def dissem_admitted_mask(state: DissemState) -> jax.Array:
    """bool[G, W]: slots with any dissemination state — at least one
    recorded holder or an already-stable flag. The dissemination half of
    the epoch-membership layer's admitted test (``repro.engine.epochs``):
    a slot whose batch is partially replicated must carry its hold bitset
    to the new owner group so the stability gate never regresses, even if
    the ordering side has not seen an id-multicast for it yet."""
    return jnp.any(state.hold_bits != 0, axis=-1) | state.stable


def unstable_backlog(state: DissemState) -> jax.Array:
    """int32[G]: admitted-but-not-yet-stable slots per group.

    The dissemination-side lag metric of ``repro.engine.adaptive``'s
    ``"unstable"`` policy: slots that carry replication state (some
    disseminator holds the batch) but have not crossed the stability
    majority, so their phase-2b votes are still being masked by the gate
    — a deep backlog here means the group's ordering output is about to
    lag and it should absorb extra traffic tiles per merged pass."""
    return jnp.sum(dissem_admitted_mask(state) & ~state.stable,
                   axis=-1, dtype=jnp.int32)
