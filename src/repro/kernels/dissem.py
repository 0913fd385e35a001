"""Pallas TPU kernel: fused dissemination-stability pass with per-group
newly-stable reduction.

The HT-Paxos dissemination layer's hot predicate (§4.1 steps 15–20 +
step 36's precondition): a batch_id is *stable* once a majority of its
group's disseminator partition holds the batch. Over a window of W
in-flight ids per ordering group this is the same dense-tile shape as the
ordering-side quorum kernel (``repro.kernels.quorum``):

    new_bits  = hold_bits | update            (uint32 [G, W, WORDS])
    counts    = Σ_words popcount(new_bits)
    stable'   = stable | (counts >= majority)
    newly[g]  = Σ_window (stable' & ~stable)   (per-group reduction)

One launch ticks every group on the ``quorum_update_grouped`` 2-D
(group, window-block) grid. The extra output vs the quorum kernel is the
per-group **newly-stable count**, accumulated across a group's window
blocks inside the kernel (``@pl.when`` init on the first block — the
window axis is the fastest grid dimension, so all of a group's blocks
revisit the same output row consecutively). The gating layer
(``repro.engine.sharded`` gated ticks) uses it as its cheap "did any id
become orderable this tick" signal without a second host-side pass.

Block sizing and BlockSpecs are shared with ``repro.kernels.quorum``,
so any window shape launches without caller-side padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quorum import DEFAULT_BLOCK_W, _grouped_specs, _pick_block_w, _rows


def _stability_kernel(bits_ref, update_ref, stable_in_ref,
                      bits_out_ref, counts_ref, stable_out_ref, newly_ref,
                      *, majority: int):
    i = pl.program_id(1)                      # window-block index
    new = bits_ref[...] | update_ref[...]
    bits_out_ref[...] = new
    counts = jnp.sum(jax.lax.population_count(new).astype(jnp.int32),
                     axis=-1)
    counts_ref[...] = counts
    prev = stable_in_ref[...]
    now = prev | (counts >= majority)
    stable_out_ref[...] = now
    newly = jnp.sum((now & ~prev).astype(jnp.int32))

    @pl.when(i == 0)
    def _init():
        newly_ref[...] = jnp.zeros_like(newly_ref)

    newly_ref[...] += newly


@functools.partial(jax.jit,
                   static_argnames=("majority", "block_w", "interpret"))
def stability_update_grouped(bits: jax.Array, update: jax.Array,
                             stable: jax.Array, *, majority: int,
                             block_w: int = DEFAULT_BLOCK_W,
                             interpret: bool = False):
    """bits/update: uint32[G, W, WORDS]; stable: bool[G, W].
    Returns (new_bits, counts int32[G, W], new_stable bool[G, W],
    newly int32[G] — ids crossing the majority threshold this call).
    ``interpret=True`` runs the kernel body in Python (the CPU test
    path)."""
    G, W, WORDS = bits.shape
    block_w = _pick_block_w(W, block_w)
    tile, row = _grouped_specs(block_w, WORDS)
    kernel = functools.partial(_stability_kernel, majority=majority)
    bits, counts, stable, newly = pl.pallas_call(
        kernel,
        grid=(G, W // block_w),
        in_specs=[tile, tile, row],
        # newly: one (1, 1) block per group, its last two dimensions
        # whole, revisited by all of the group's window blocks in turn
        out_specs=[tile, row, row,
                   pl.BlockSpec((None, 1, 1), lambda g, i: (g, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((G, W, WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((G, 1, W), jnp.int32),
            jax.ShapeDtypeStruct((G, 1, W), jnp.bool_),
            jax.ShapeDtypeStruct((G, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(bits, update, _rows(stable))
    return bits, counts[:, 0], stable[:, 0], newly[:, 0, 0]
