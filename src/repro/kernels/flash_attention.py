"""Pallas TPU kernel: blockwise causal flash attention (GQA-aware).

Grid: (batch·kv_heads·groups, n_q_blocks, n_kv_blocks) — the kv-block dim
iterates innermost on TPU, so the online-softmax running state (m, l, acc)
lives in VMEM scratch and persists across kv steps of one q block.

Block shapes are (BLOCK_Q, head_dim) / (BLOCK_K, head_dim) with
MXU-aligned defaults (128); the q·kᵀ tile is [BLOCK_Q, BLOCK_K] f32 in
VMEM. Causal + sliding-window masking is computed from program ids, and
fully-masked kv blocks are skipped with ``pl.when`` (the big win for
sliding-window archs — hymba's window=1024 touches ≤ 2 kv blocks/q block).

Validated in interpret mode against ``repro.models.layers.flash_attend``
(itself validated against the direct-softmax oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level reachability: any (q, k) pair with k ≤ q and within window
    reachable = True
    if causal:
        reachable = k_start <= q_start + block_q - 1
    if window > 0:
        reachable = jnp.logical_and(
            reachable, k_start + block_k - 1 > q_start - window)

    @pl.when(reachable)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [block_q, h]
        k = k_ref[0].astype(jnp.float32)          # [block_k, h]
        v = v_ref[0].astype(jnp.float32)          # [block_k, hv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, -1e30)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _fin():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = -1,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: [B,Sq,H,h]; k/v: [B,Skv,K,h|hv]; GQA via H = K·G. Returns
    [B,Sq,H,hv]."""
    B, Sq, H, h = q.shape
    _, Skv, K, hv = v.shape
    G = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k
    # flatten (B,K,G) into one grid dim; kv shared across G
    qf = q.reshape(B, Sq, K, G, h).transpose(0, 2, 3, 1, 4) \
        .reshape(B * K * G, Sq, h)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(B * K, Skv, h),
                    G, axis=0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(B * K, Skv, hv),
                    G, axis=0)
    grid = (B * K * G, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=1.0 / np.sqrt(h), causal=causal,
        window=window, block_q=block_q, block_k=block_k, n_kv=nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, h), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, h), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hv),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K * G, Sq, hv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hv), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, K, G, Sq, hv).transpose(0, 3, 1, 2, 4) \
        .reshape(B, Sq, H, hv)
