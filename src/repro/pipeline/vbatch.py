"""Vectorized byte-budget batch accumulation (§4.1 step 13) in jax.

``repro.dissem.batcher`` defines the batching semantics twice on the
host side (``plan_batches`` one-shot, ``BatchAccumulator`` streaming);
this module is the third, ``lax.scan``-able twin the closed pipeline
jits: one :func:`batch_step` per request, vmapped across disseminator
lanes, with the accumulator registers (``used`` wire bytes, ``count``
requests, ``seq`` next batch number) carried as a :class:`BatchState`
pytree from tick to tick.

Semantics are copied exactly from ``BatchAccumulator.add``: a request
of payload ``s`` costs ``ID_BYTES + s`` on the wire; it *closes* the
open batch first iff the batch is non-empty and either the cost would
push past ``budget_bytes`` or the batch already holds ``max_requests``
— so a single oversized request still gets a batch of its own, and
request order is preserved. Equality with ``plan_batches`` over any
size stream is property-tested (``tests/test_pipeline.py``).

:func:`tick_flushes` adds the per-tick tail flush (the DES twin's
``batch_linger == 0``: a disseminator's pending tail is flushed by the
linger timer in the same instant the requests arrived), emitting at
most ``K + 1`` batches per lane per tick for ``K`` request slots —
overflow closures at their stream positions first, the tail last,
matching the order a DES disseminator multicasts them.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.network import ID_BYTES
from ..dissem.batcher import EMPTY_BATCH_BYTES
from ..engine import stages

_NO_CAP = 1 << 30       # max_requests=None sentinel (count never reaches it)


class BatchState(NamedTuple):
    """Per-disseminator-lane accumulator registers (all int32[D])."""
    used: jax.Array     # wire bytes of the open batch, incl. header
    count: jax.Array    # requests in the open batch
    seq: jax.Array      # next batch sequence number to assign


def init_batch_state(n_lanes: int) -> BatchState:
    return BatchState(
        used=jnp.full((n_lanes,), EMPTY_BATCH_BYTES, jnp.int32),
        count=jnp.zeros((n_lanes,), jnp.int32),
        seq=jnp.zeros((n_lanes,), jnp.int32))


def batch_step(carry, size, valid, *, budget_bytes: int,
               max_requests: int | None):
    """One ``BatchAccumulator.add`` as a scan step (scalar lane).

    carry: ``(used, count, seq)`` int32 scalars. Returns the new carry
    and ``(closed, closed_seq, closed_count, closed_bytes)`` — the batch
    flushed *by* this request (valid only where ``closed``). The request
    itself joins the (possibly fresh) open batch."""
    used, count, seq = carry
    cap = _NO_CAP if max_requests is None else int(max_requests)
    cost = jnp.int32(ID_BYTES) + size
    closed = valid & (count > 0) & (
        (used + cost > budget_bytes) | (count >= cap))
    closed_seq, closed_count, closed_bytes = seq, count, used
    seq = jnp.where(closed, seq + 1, seq)
    used = jnp.where(closed, jnp.int32(EMPTY_BATCH_BYTES), used)
    count = jnp.where(closed, 0, count)
    used = jnp.where(valid, used + cost, used)
    count = jnp.where(valid, count + 1, count)
    return (used, count, seq), (closed, closed_seq, closed_count,
                                closed_bytes)


class TickFlushes(NamedTuple):
    """Batches flushed by one lane-tick, in flush order.

    Position ``i < K`` is the batch closed by request slot ``i``
    (overflow closure); position ``K`` is the end-of-tick tail flush.
    ``req_seq[i]`` is the batch each *request* was assigned to — the
    vectorized mirror of ``plan_batches``' assignment array."""
    valid: jax.Array    # bool[..., K+1]
    seq: jax.Array      # int32[..., K+1]
    count: jax.Array    # int32[..., K+1]
    bytes: jax.Array    # int32[..., K+1] wire bytes incl. header
    req_seq: jax.Array  # int32[..., K]


def _tick_lane(state, sizes, valid, *, budget_bytes, max_requests,
               flush_tail):
    def step(carry, x):
        return batch_step(carry, x[0], x[1], budget_bytes=budget_bytes,
                          max_requests=max_requests)

    carry = (state.used, state.count, state.seq)
    (used, count, seq), (closed, cseq, ccount, cbytes) = jax.lax.scan(
        step, carry, (sizes, valid))
    # request i joined the batch that was open *after* its closure check:
    # seq at that moment == closed-batch seq + closures at positions <= i
    req_seq = state.seq + jnp.cumsum(closed.astype(jnp.int32))
    if flush_tail:
        tail = count > 0
        out = TickFlushes(
            valid=jnp.concatenate([closed, tail[None]]),
            seq=jnp.concatenate([cseq, seq[None]]),
            count=jnp.concatenate([ccount, count[None]]),
            bytes=jnp.concatenate([cbytes, used[None]]),
            req_seq=req_seq)
        seq = jnp.where(tail, seq + 1, seq)
        used = jnp.where(tail, jnp.int32(EMPTY_BATCH_BYTES), used)
        count = jnp.where(tail, 0, count)
    else:
        pad = jnp.zeros((1,), closed.dtype), jnp.zeros((1,), jnp.int32)
        out = TickFlushes(
            valid=jnp.concatenate([closed, pad[0]]),
            seq=jnp.concatenate([cseq, pad[1]]),
            count=jnp.concatenate([ccount, pad[1]]),
            bytes=jnp.concatenate([cbytes, pad[1]]),
            req_seq=req_seq)
    return BatchState(used, count, seq), out


def tick_flushes(state: BatchState, sizes: jax.Array, valid: jax.Array,
                 *, budget_bytes: int, max_requests: int | None = None,
                 flush_tail: bool = True)\
        -> tuple[BatchState, TickFlushes]:
    """One tick of request intake across all lanes.

    ``sizes``/``valid``: int32/bool[D, K] — lane-major request slots in
    client order. ``flush_tail=True`` is the linger-0 contract (every
    open batch flushes at end of tick); ``False`` carries the open batch
    into the next tick (nonzero linger — :class:`TickFlushes` then only
    reports overflow closures)."""
    if budget_bytes <= EMPTY_BATCH_BYTES:
        raise ValueError(
            f"budget_bytes={budget_bytes} cannot fit the batch header "
            f"({EMPTY_BATCH_BYTES} B) plus any request")
    fn = jax.vmap(
        lambda st, s, v: _tick_lane(st, s, v, budget_bytes=budget_bytes,
                                    max_requests=max_requests,
                                    flush_tail=flush_tail))
    with jax.named_scope(stages.BATCHER):
        return fn(state, sizes, valid)
