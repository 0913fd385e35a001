"""Pallas TPU kernel: chunked WKV6 scan (RWKV6 time-mix hot loop).

Per (batch·head) lane, the recurrence
    S_t = diag(w_t) S_{t-1} + k_tᵀ v_t ;   o_t = r_t (S_{t-1} + u⊙k_tᵀ v_t)
is evaluated in chunks of C tokens: three [C,·] matmuls (MXU) per chunk
plus a rank-C state update, with the [hd, hd] f32 state held in VMEM
scratch across the chunk dimension of the grid (innermost → sequential).

Grid: (B·H, n_chunks). Block shapes: r/k/v/w chunks are [C, hd]; the
log-decay cumulative sums are computed in-kernel in f32 (numerically
sensitive — same layout as the jnp reference in models.ssm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_scr,
                 *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)          # [C, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    wlog = w_ref[0].astype(jnp.float32)       # [C, hd] log-decay (< 0)
    u = u_ref[0].astype(jnp.float32)          # [1, hd] bonus

    cum = jnp.cumsum(wlog, axis=0)
    cum_ex = cum - wlog
    total = cum[-1:, :]                       # [1, hd]
    q_dec = r * jnp.exp(cum_ex)
    k_dec = k * jnp.exp(-cum)
    att = jax.lax.dot_general(q_dec, k_dec, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    att = jnp.where(tri, att, 0.0)
    diag = jnp.sum(r * (u * k), axis=1)       # bonus: r_t·(u⊙k_t)
    intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) \
        + diag[:, None] * v
    inter = jax.lax.dot_general(q_dec, state_scr[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = (intra + inter).astype(o_ref.dtype)
    # state update: S ← diag(exp(total)) S + Σ_s exp(total - cum_s) k_s ⊗ v_s
    k_carry = k * jnp.exp(total - cum)
    state_scr[...] = (jnp.exp(total).T * state_scr[...]
                      + jax.lax.dot_general(
                          k_carry, v, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def wkv6_chunked(r, k, v, wlog, u, *, chunk: int = 128,
                 interpret: bool = False):
    """r/k/v/wlog: [B,S,H,hd] (wlog = log decay, f32-representable);
    u: [H, hd] bonus. Returns [B,S,H,hd] f32 WKV output (pre-gate)."""
    B, S, H, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    NC = S // chunk

    def flat(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    rf, kf, vf, wf = map(flat, (r, k, v, wlog))
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    grid = (B * H, NC)
    out = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
