"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp
oracle, swept over shapes and dtypes, plus property tests on the quorum
engine's invariants (hypothesis when installed, deterministic seeded
draws otherwise — see _hypothesis_compat)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import jaxsim
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quorum import quorum_update, quorum_update_grouped
from repro.kernels.rwkv6_scan import wkv6_chunked


# ---------------------------------------------------------------------------
# quorum kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W,D", [(64, 33), (256, 100), (512, 1000)])
@pytest.mark.parametrize("block_w", [64, 256])
def test_quorum_kernel_shapes(W, D, block_w):
    if W % min(block_w, W):
        pytest.skip("block must divide W")
    words = (D + 31) // 32
    rng = np.random.default_rng(W + D)
    bits = jnp.asarray(rng.integers(0, 2**32, (W, words), dtype=np.uint32))
    upd = jnp.asarray(rng.integers(0, 2**32, (W, words), dtype=np.uint32))
    stable = jnp.asarray(rng.random(W) < 0.2)
    maj = D // 2 + 1
    got = quorum_update(bits, upd, stable, majority=maj,
                        block_w=min(block_w, W), interpret=True)
    want = ref.quorum_ref(bits, upd, stable, majority=maj)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 1000), d=st.integers(1, 200))
def test_quorum_threshold_property(seed, d):
    """stable ⇔ popcount ≥ majority, monotone under more acks."""
    rng = np.random.default_rng(seed)
    W = 64
    words = (d + 31) // 32
    acks = rng.random((W, d)) < rng.random()
    packed = jaxsim.pack_tile(jnp.asarray(acks))
    maj = d // 2 + 1
    _, counts, stable = quorum_update(
        packed, jnp.zeros_like(packed), jnp.zeros((W,), jnp.bool_),
        majority=maj, block_w=64, interpret=True)
    want = jaxsim.oracle_quorum(acks, maj)
    assert np.array_equal(np.asarray(stable), want)
    assert np.array_equal(np.asarray(counts), acks.sum(1))


@pytest.mark.parametrize("G,W,D", [
    (2, 12, 32),     # non-8-aligned window, exact 1-word boundary
    (3, 20, 33),     # non-8-aligned window, word + 1 bit
    (1, 7, 31),      # window smaller than a sublane tile, word − 1 bit
    (2, 36, 65),     # non-8-aligned window, 2 words + 1 bit
    (4, 10, 1),      # degenerate single-disseminator bitset
    (2, 24, 64),     # exact 2-word boundary
])
def test_quorum_kernel_grouped_edge_shapes_vs_packed_core(G, W, D):
    """Parity at awkward shapes: non-8-aligned window sizes and WORDS
    boundaries, grouped kernel (interpret mode, block_w auto-clamped to a
    divisor of W) vs the jaxsim packed-core reference — the exact math the
    sharded engine vmaps, and the tiles window recycling remaps around
    (the kernel itself stays oblivious to recycling)."""
    words = (D + 31) // 32
    rng = np.random.default_rng(G * 1000 + W * 10 + D)
    bits = jnp.asarray(rng.integers(0, 2**32, (G, W, words), dtype=np.uint32))
    upd = jnp.asarray(rng.integers(0, 2**32, (G, W, words), dtype=np.uint32))
    stable = jnp.asarray(rng.random((G, W)) < 0.3)
    maj = D // 2 + 1
    new_bits, counts, new_stable = quorum_update_grouped(
        bits, upd, stable, majority=maj, interpret=True)
    # reference: the un-jitted packed core of the single-group engine,
    # vmapped along G exactly as repro.engine.sharded does
    st = jaxsim.QuorumState(
        ack_bits=bits, vote_bits=jnp.zeros((G, W, 1), jnp.uint32),
        stable=stable, instance=jnp.full((G, W), -1, jnp.int32),
        decided=jnp.zeros((G, W), jnp.bool_),
        next_instance=jnp.zeros((G,), jnp.int32))
    want = jax.vmap(
        lambda s, u: jaxsim.absorb_acks_packed(s, u, maj))(st, upd)
    assert np.array_equal(np.asarray(new_bits), np.asarray(want.ack_bits))
    assert np.array_equal(np.asarray(new_stable), np.asarray(want.stable))
    assert np.array_equal(np.asarray(counts),
                          np.asarray(jax.vmap(jaxsim.popcount_rows)(
                              want.ack_bits)))


def test_quorum_kernel_single_group_odd_window():
    """1-D launch at a non-dividing block size: a window with no
    128-lane divisor runs as one block instead of asserting."""
    W, D = 40, 100
    words = (D + 31) // 32
    rng = np.random.default_rng(40)
    bits = jnp.asarray(rng.integers(0, 2**32, (W, words), dtype=np.uint32))
    upd = jnp.asarray(rng.integers(0, 2**32, (W, words), dtype=np.uint32))
    stable = jnp.zeros((W,), jnp.bool_)
    got = quorum_update(bits, upd, stable, majority=D // 2 + 1,
                        block_w=16, interpret=True)   # 16 ∤ 40 → one block
    want = ref.quorum_ref(bits, upd, stable, majority=D // 2 + 1)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,K,h,hv,window", [
    (128, 4, 4, 32, 32, -1),     # MHA
    (256, 8, 4, 64, 64, -1),     # GQA
    (256, 8, 4, 64, 64, 100),    # sliding window
    (128, 4, 2, 48, 32, -1),     # MLA-style hv != h
])
def test_flash_kernel_vs_ref(S, H, K, h, hv, window, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(S + H + h), 3)
    q = jax.random.normal(ks[0], (B, S, H, h), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, h), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, hv), jnp.float32).astype(dtype)
    got = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert err < tol, err


def test_flash_kernel_block_shape_sweep():
    B, S, H, K, h = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, h), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, h), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, h), jnp.float32)
    want = ref.flash_attention_ref(q, k, v)
    for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        got = flash_attention(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 2e-5, (bq, bk, err)


# ---------------------------------------------------------------------------
# rwkv6 chunked scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,hd,chunk", [
    (64, 2, 32, 16), (128, 4, 64, 32), (64, 1, 128, 64),
])
def test_wkv6_kernel_vs_sequential(S, H, hd, chunk, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 5)
    r = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32).astype(dtype)
    wlog = (-jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, hd)))
            - 1e-4).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.1)
    got = wkv6_chunked(r, k, v, wlog, u, chunk=chunk, interpret=True)
    want = ref.wkv6_ref(r, k, v, wlog, u)
    scale = float(jnp.max(jnp.abs(want))) + 1.0
    tol = (1e-5 if dtype == jnp.float32 else 3e-3) * scale
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < tol, (err, scale)


# ---------------------------------------------------------------------------
# vectorized protocol engine (jax.lax reference of the quorum kernel)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 500), W=st.sampled_from([32, 128]),
       D=st.integers(3, 64), S=st.integers(3, 9),
       ticks=st.integers(1, 5))
def test_engine_invariants(seed, W, D, S, ticks):
    rng = np.random.default_rng(seed)
    st_ = jaxsim.init_state(W, D, S)
    dm, sm = D // 2 + 1, S // 2 + 1
    acc = np.zeros((W, D), bool)
    for _ in range(ticks):
        acks = rng.random((W, D)) < 0.3
        votes = rng.random((W, S)) < 0.5
        acc |= acks
        st_, out = jaxsim.engine_tick(
            st_, jnp.asarray(acks), jnp.asarray(votes),
            diss_majority=dm, seq_majority=sm)
        # instances are consecutive, assigned exactly once, stable-only
        inst = np.asarray(st_.instance)
        got = sorted(inst[inst >= 0].tolist())
        assert got == list(range(len(got)))
        assert np.array_equal(np.asarray(st_.stable),
                              jaxsim.oracle_quorum(acc, dm))
        # decided ⇒ ordered
        assert not np.any(np.asarray(st_.decided) & (inst < 0))
