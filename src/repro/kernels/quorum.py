"""Pallas TPU kernel: fused ack-bitset OR + popcount + majority threshold.

The HT-Paxos sequencer hot path (§4.1 step 36: "upon receiving same
<request_id> from at least a majority of disseminators") over a window of
W in-flight ids. The GPU idiom would be one atomic per (id, disseminator)
ack; the TPU idiom is a dense VMEM tile pass:

    new_bits = bits | update          (uint32 [W, WORDS])
    counts   = Σ_words popcount(new_bits)
    stable  |= counts >= majority

One kernel launch processes a [BLOCK_W, WORDS] tile per grid step. The
word axis is the lane dimension and stays whole (WORDS ≤ 32 for 1000
disseminators, under a VREG row; Mosaic masks the sub-128 lanes).

The kernel is completely oblivious to the engine's window recycling
(``repro.engine.sharded.RecycleState``): compaction/refill is host-side
slot remapping *around* the kernel's grid — the kernel always sees a
dense ``[W, WORDS]`` (or grouped ``[G, W, WORDS]``) tile and neither
knows nor cares which global id a row currently holds. Window blocks
are multiples of 128 lanes that divide W; a window with none (e.g. odd,
non-8-aligned sizes) runs as one block per group, so any window shape
launches without caller-side padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_W = 256


def _pick_block_w(W: int, block_w: int) -> int:
    """Pick a window block size that divides W.

    The window is the lane (last) axis of the ``[G, W]`` counts/stable
    operands, so Mosaic takes a window block only when it is a multiple
    of 128 lanes or the whole window. Preference order: the largest
    multiple of 128 that divides W and is at most ``max(block_w, 128)``,
    else W itself in a single block per group."""
    b = max(block_w, 128)
    for cand in range(b - b % 128, 0, -128):
        if W % cand == 0:
            return cand
    return W


def _quorum_kernel(bits_ref, update_ref, stable_in_ref,
                   bits_out_ref, counts_ref, stable_out_ref,
                   *, majority: int):
    # one group's [1, BLOCK_W, WORDS] bit tile and [1, BLOCK_W] slot
    # rows; words are the last axis
    new = bits_ref[...] | update_ref[...]
    bits_out_ref[...] = new
    counts = jnp.sum(jax.lax.population_count(new).astype(jnp.int32),
                     axis=-1)
    counts_ref[...] = counts
    stable_out_ref[...] = stable_in_ref[...] | (counts >= majority)


def _grouped_specs(block_w: int, words: int):
    """BlockSpecs of the (group, window-block) grid, shared with
    ``repro.kernels.dissem``. Mosaic requires a block's last two
    dimensions to be (8k, 128k) or whole, so per-slot rows travel as
    ``[G, 1, W]`` (see :func:`_rows`): a ``(1, BLOCK_W)`` block of a
    ``[G, W]`` array would split the group axis into single sublanes."""
    tile = pl.BlockSpec((1, block_w, words), lambda g, i: (g, i, 0))
    row = pl.BlockSpec((None, 1, block_w), lambda g, i: (g, 0, i))
    return tile, row


def _rows(x: jax.Array) -> jax.Array:
    """``[G, W]`` per-slot rows in the kernels' ``[G, 1, W]`` layout."""
    return x[:, None, :]


@functools.partial(jax.jit,
                   static_argnames=("majority", "block_w", "interpret"))
def quorum_update(bits: jax.Array, update: jax.Array, stable: jax.Array,
                  *, majority: int, block_w: int = DEFAULT_BLOCK_W,
                  interpret: bool = False):
    """bits/update: uint32[W, WORDS]; stable: bool[W].
    Returns (new_bits, counts int32[W], new_stable bool[W]).

    The single-group launch of :func:`quorum_update_grouped` (G=1): a
    1-D ``[W]`` operand's XLA layout tiles 1024 lanes, which window blocks
    of 1-D arrays would have to match, while the grouped ``[1, W]`` rows
    take any 128-lane block. ``interpret=True`` runs the kernel body in
    Python (the CPU test path)."""
    new, counts, st = quorum_update_grouped(
        bits[None], update[None], stable[None], majority=majority,
        block_w=block_w, interpret=interpret)
    return new[0], counts[0], st[0]


@functools.partial(jax.jit,
                   static_argnames=("majority", "block_w", "interpret"))
def quorum_update_grouped(bits: jax.Array, update: jax.Array,
                          stable: jax.Array, *, majority: int,
                          block_w: int = DEFAULT_BLOCK_W,
                          interpret: bool = False):
    """Multi-group extension: bits/update uint32[G, W, WORDS], stable
    bool[G, W] — one launch ticks every ordering group of the sharded
    engine (``repro.engine.sharded``) on a 2-D (group, window-block) grid.
    Returns (new_bits, counts int32[G, W], new_stable bool[G, W]).

    The group axis maps to the leading grid dimension so each group's
    window blocks stay contiguous in VMEM."""
    G, W, WORDS = bits.shape
    block_w = _pick_block_w(W, block_w)
    tile, row = _grouped_specs(block_w, WORDS)
    kernel = functools.partial(_quorum_kernel, majority=majority)
    new, counts, stable = pl.pallas_call(
        kernel,
        grid=(G, W // block_w),
        in_specs=[tile, tile, row],
        out_specs=[tile, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((G, W, WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((G, 1, W), jnp.int32),
            jax.ShapeDtypeStruct((G, 1, W), jnp.bool_),
        ],
        interpret=interpret,
    )(bits, update, _rows(stable))
    return new, counts[:, 0], stable[:, 0]
