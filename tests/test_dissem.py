"""repro.dissem unit suite: stability engine vs numpy oracle, fused
Pallas kernel parity, batch accumulation properties, and the per-node
bandwidth accounting against the §5.2 closed forms (partitioned vs
global disseminator sets, Figs 4–7)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.analytical import (bytes_ht_disseminator,
                                   bytes_ht_disseminator_partitioned)
from repro.core.htpaxos import batch_bytes
from repro.dissem import (ACK_BYTES, BatchAccumulator, EMPTY_BATCH_BYTES,
                          batch_wire_sizes, init_dissem, partition_size,
                          per_node_bytes, plan_batches,
                          replication_bytes_per_node, run_stability_ticks,
                          stability_tick, stability_tick_dense,
                          stability_tick_fused, stable_ids, uniform_traffic)
from repro.dissem.engine import unpack_tile


def _rand_packed(rng, T, G, W, n):
    words = (n + 31) // 32
    packed = rng.integers(0, 2**32, (T, G, W, words), dtype=np.uint32)
    # clear the bits past n in the last word
    tail = n % 32
    if tail:
        packed[..., -1] &= np.uint32((1 << tail) - 1)
    return packed


def _popcount(a):
    return np.unpackbits(
        a.astype(np.uint32).view(np.uint8), axis=-1,
        bitorder="little").sum(axis=-1, dtype=np.int32)


class TestStabilityEngine:
    def test_tick_matches_numpy_oracle(self):
        rng = np.random.default_rng(7)
        G, W, n, T = 3, 24, 37, 5          # n > 32: two uint32 words
        seq = _rand_packed(rng, T, G, W, n)
        maj = n // 2 + 1
        st_ = init_dissem(G, W, n)
        acc = np.zeros((G, W, (n + 31) // 32), np.uint32)
        stable = np.zeros((G, W), bool)
        for t in range(T):
            st_, out = stability_tick(st_, jnp.asarray(seq[t]), majority=maj)
            acc |= seq[t]
            counts = _popcount(acc)
            new_stable = stable | (counts >= maj)
            assert (np.asarray(st_.hold_bits) == acc).all()
            assert (np.asarray(out["counts"]) == counts).all()
            assert (np.asarray(st_.stable) == new_stable).all()
            assert (np.asarray(out["newly_stable"])
                    == (new_stable & ~stable)).all()
            stable = new_stable

    def test_stability_is_monotone_and_scan_matches_loop(self):
        rng = np.random.default_rng(11)
        G, W, n, T = 2, 16, 5, 8
        seq = _rand_packed(rng, T, G, W, n)
        maj = 3
        st_loop = init_dissem(G, W, n)
        prev = np.zeros((G, W), bool)
        for t in range(T):
            st_loop, _ = stability_tick(st_loop, jnp.asarray(seq[t]),
                                        majority=maj)
            now = np.asarray(st_loop.stable)
            assert (now | prev == now).all(), "stability must be monotone"
            prev = now
        st_scan, outs = run_stability_ticks(
            init_dissem(G, W, n), jnp.asarray(seq), majority=maj)
        assert (np.asarray(st_scan.hold_bits)
                == np.asarray(st_loop.hold_bits)).all()
        assert (np.asarray(st_scan.stable) == np.asarray(st_loop.stable)).all()
        # the stacked newly_stable schedule partitions the final stable set
        sched = np.asarray(outs["newly_stable"])
        assert (sched.sum(0) == np.asarray(st_scan.stable)).all()
        assert (sched.sum(0) <= 1).all()

    def test_dense_wrapper_and_unpack_roundtrip(self):
        rng = np.random.default_rng(3)
        G, W, n = 2, 8, 7
        holds = rng.integers(0, 2, (G, W, n)).astype(bool)
        s1, o1 = stability_tick_dense(init_dissem(G, W, n),
                                      jnp.asarray(holds), majority=4)
        assert (np.asarray(unpack_tile(s1.hold_bits, n)) == holds).all()
        assert (np.asarray(o1["counts"]) == holds.sum(-1)).all()

    def test_pre_stable_and_stable_ids(self):
        G, W, n = 2, 6, 5
        st_ = init_dissem(G, W, n, pre_stable=True)
        assert bool(st_.stable.all())
        ids = jnp.arange(G * W, dtype=jnp.int32).reshape(G, W)
        assert (np.asarray(stable_ids(st_, ids)) == np.asarray(ids)).all()
        st0 = init_dissem(G, W, n)
        assert (np.asarray(stable_ids(st0, ids)) == -1).all()

    @pytest.mark.parametrize("G,W,n,block_w", [
        (1, 8, 5, 8), (2, 24, 5, 8), (3, 16, 37, 4), (2, 10, 33, 256),
        # several 128-lane window blocks per group: `newly` accumulates
        (2, 256, 37, 128), (1, 384, 5, 128)])
    def test_fused_kernel_matches_reference(self, G, W, n, block_w):
        rng = np.random.default_rng(G * 100 + W)
        packed = _rand_packed(rng, 2, G, W, n)
        maj = n // 2 + 1
        # second tick starts from non-trivial carried state on both paths
        ref0, _ = stability_tick(init_dissem(G, W, n),
                                 jnp.asarray(packed[0]), majority=maj)
        ref, oref = stability_tick(ref0, jnp.asarray(packed[1]), majority=maj)
        fus0, _ = stability_tick_fused(init_dissem(G, W, n),
                                       jnp.asarray(packed[0]), majority=maj,
                                       block_w=block_w, interpret=True)
        fus, ofus = stability_tick_fused(fus0, jnp.asarray(packed[1]),
                                         majority=maj, block_w=block_w,
                                         interpret=True)
        assert (np.asarray(ref.hold_bits) == np.asarray(fus.hold_bits)).all()
        assert (np.asarray(ref.stable) == np.asarray(fus.stable)).all()
        assert (np.asarray(oref["counts"]) == np.asarray(ofus["counts"])).all()
        # the kernel's on-chip per-group reduction equals the host count
        assert (np.asarray(ofus["newly_per_group"])
                == np.asarray(oref["newly_stable"]).sum(1)).all()


class TestBatcher:
    def test_plan_batches_known_case(self):
        a = plan_batches([10, 20, 300, 5, 5, 5], budget_bytes=200)
        assert a.tolist() == [0, 0, 1, 2, 2, 2]
        sizes = batch_wire_sizes([10, 20, 300, 5, 5, 5], a)
        assert sizes.tolist() == [
            EMPTY_BATCH_BYTES + 4 + 10 + 4 + 20,
            EMPTY_BATCH_BYTES + 4 + 300,
            EMPTY_BATCH_BYTES + 3 * (4 + 5)]

    def test_budget_too_small_raises(self):
        with pytest.raises(ValueError):
            plan_batches([1], budget_bytes=EMPTY_BATCH_BYTES)
        with pytest.raises(ValueError):
            BatchAccumulator(budget_bytes=EMPTY_BATCH_BYTES)


@given(sizes=st.lists(st.integers(min_value=0, max_value=400),
                      min_size=0, max_size=40),
       budget=st.integers(min_value=EMPTY_BATCH_BYTES + 1, max_value=600),
       maxreq=st.sampled_from([None, 1, 3, 7]))
@settings(max_examples=40, deadline=None)
def test_plan_batches_properties(sizes, budget, maxreq):
    a = plan_batches(sizes, budget_bytes=budget, max_requests=maxreq)
    if not sizes:
        assert len(a) == 0
        return
    # batch indices are a non-decreasing 0-based contiguous sequence
    assert a[0] == 0
    assert (np.diff(a) >= 0).all() and (np.diff(a) <= 1).all()
    wire = batch_wire_sizes(sizes, a)
    counts = np.bincount(a)
    for b, w in enumerate(wire):
        # budget respected unless the batch is a single oversized request
        assert w <= budget or counts[b] == 1
        if maxreq is not None:
            assert counts[b] <= maxreq
    # total wire bytes = per-request costs + one header per batch
    assert wire.sum() == (len(wire) * EMPTY_BATCH_BYTES
                          + sum(4 + s for s in sizes))


@given(sizes=st.lists(st.integers(min_value=0, max_value=400),
                      min_size=0, max_size=40),
       budget=st.integers(min_value=EMPTY_BATCH_BYTES + 1, max_value=600),
       maxreq=st.sampled_from([None, 1, 3, 7]))
@settings(max_examples=40, deadline=None)
def test_accumulator_equals_plan_batches(sizes, budget, maxreq):
    a = plan_batches(sizes, budget_bytes=budget, max_requests=maxreq)
    planned = [[sizes[i] for i in range(len(sizes)) if a[i] == b]
               for b in range(int(a.max()) + 1 if len(a) else 0)]
    acc = BatchAccumulator(budget_bytes=budget, max_requests=maxreq)
    streamed = []
    for s in sizes:
        f = acc.add(s)
        if f is not None:
            streamed.append(f)
    tail = acc.flush()
    if tail is not None:
        streamed.append(tail)
    assert streamed == planned
    assert acc.n_flushed == len(planned)
    assert acc.bytes_flushed == batch_wire_sizes(sizes, a).sum()
    assert acc.pending_bytes == 0


@given(sizes=st.lists(st.integers(min_value=0, max_value=400),
                      min_size=0, max_size=40),
       budget=st.integers(min_value=EMPTY_BATCH_BYTES + 1, max_value=600),
       maxreq=st.sampled_from([None, 1, 3, 7]))
@settings(max_examples=40, deadline=None)
def test_accumulator_byte_accounting_invariant(sizes, budget, maxreq):
    """Accounting invariant, held at EVERY point of the stream (not just
    after the final flush): ``bytes_flushed + pending_bytes`` equals the
    total wire bytes of the equivalent one-shot plan over the requests
    seen so far. Greedy batching is prefix-stable, so the streaming and
    planned totals can never diverge mid-stream — this is what lets the
    bandwidth closed forms consume either implementation's numbers."""
    acc = BatchAccumulator(budget_bytes=budget, max_requests=maxreq)

    def planned_total(k):
        if k == 0:
            return 0
        a = plan_batches(sizes[:k], budget_bytes=budget,
                         max_requests=maxreq)
        return int(batch_wire_sizes(sizes[:k], a).sum())

    assert acc.bytes_flushed + acc.pending_bytes == 0
    for k, s in enumerate(sizes, start=1):
        acc.add(s)
        assert acc.bytes_flushed + acc.pending_bytes == planned_total(k)
    acc.flush()
    assert acc.pending_bytes == 0
    assert acc.bytes_flushed == planned_total(len(sizes))


def test_accumulator_accounting_oversized_and_maxreq_edges():
    """The invariant at the two flush-trigger edges: a single oversized
    request (cost > budget) gets its own over-budget batch and is counted
    at its true wire size; max_requests=1 closes a batch per request, so
    every pending batch is exactly header + one request."""
    budget = EMPTY_BATCH_BYTES + 50
    acc = BatchAccumulator(budget_bytes=budget)
    acc.add(500)                                   # oversized, atomic
    assert acc.pending_bytes == EMPTY_BATCH_BYTES + 4 + 500
    assert acc.pending_bytes > budget              # over budget by design
    acc.add(10)                                    # closes the oversized batch
    assert acc.bytes_flushed == EMPTY_BATCH_BYTES + 4 + 500
    assert acc.pending_bytes == EMPTY_BATCH_BYTES + 4 + 10
    sizes = [500, 10]
    a = plan_batches(sizes, budget_bytes=budget)
    assert acc.bytes_flushed + acc.pending_bytes == \
        batch_wire_sizes(sizes, a).sum()

    acc1 = BatchAccumulator(budget_bytes=10_000, max_requests=1)
    for k, s in enumerate([10, 20, 30], start=1):
        acc1.add(s)
        assert acc1.pending_bytes == EMPTY_BATCH_BYTES + 4 + s
        assert acc1.n_flushed == k - 1
    acc1.flush()
    a1 = plan_batches([10, 20, 30], budget_bytes=10_000, max_requests=1)
    assert int(a1.max()) + 1 == acc1.n_flushed == 3
    assert acc1.bytes_flushed == batch_wire_sizes([10, 20, 30], a1).sum()


class TestBandwidth:
    def test_partition_size(self):
        assert partition_size(12, 4) == 3
        with pytest.raises(ValueError):
            partition_size(10, 4)
        with pytest.raises(ValueError):
            uniform_traffic(1, 10, 4, batch_nbytes=100)

    def test_uniform_traffic_matches_closed_form(self):
        k, q, mp = 4, 100, 5
        b = batch_bytes(k, q)
        packed, owner, nbytes = uniform_traffic(2, 3 * mp, mp, batch_nbytes=b)
        st_, _ = stability_tick(init_dissem(2, 3 * mp, mp),
                                jnp.asarray(packed), majority=mp // 2 + 1)
        in_b, out_b = per_node_bytes(st_, owner, nbytes, mp)
        cf = replication_bytes_per_node(k, q, mp)
        # 3 owned slots per node = 3 unit times of the closed form
        assert (in_b == 3 * cf["in"]).all()
        assert (out_b == 3 * cf["out"]).all()

    def test_partial_holds_accounting(self):
        """Hand-computed 1-group case: holds below full replication."""
        G, W, n = 1, 2, 3
        holds = np.zeros((G, W, n), bool)
        holds[0, 0] = [True, True, False]      # slot 0: nodes 0,1 hold
        holds[0, 1] = [True, False, True]      # slot 1: nodes 0,2 hold
        st_, _ = stability_tick_dense(init_dissem(G, W, n),
                                      jnp.asarray(holds), majority=2)
        owner = np.array([[0, 2]], np.int32)
        nbytes = np.array([[100, 200]], np.int64)
        in_b, out_b = per_node_bytes(st_, owner, nbytes, n)
        A = ACK_BYTES
        # node0: got both batches + 2 acks for its slot-0 batch
        assert in_b[0, 0] == 100 + 200 + 2 * A
        # node1: got batch0 only; node2: batch1 + 2 acks for its batch
        assert in_b[0, 1] == 100
        assert in_b[0, 2] == 200 + 2 * A
        # out: acks per held batch + one frame per owned batch
        assert out_b[0, 0] == 2 * A + 100
        assert out_b[0, 1] == 1 * A
        assert out_b[0, 2] == 1 * A + 200

    def test_partitioned_strictly_below_global_per_node(self):
        """§5.5: same total batch load, m disseminators — partitioned into
        G groups every node sees ~G× less replication traffic."""
        m, k, q = 12, 4, 64
        b = batch_bytes(k, q)
        glob = replication_bytes_per_node(k, q, m)
        for G in (2, 3, 4):
            part = replication_bytes_per_node(k, q, partition_size(m, G))
            assert part["in"] < glob["in"]
            assert part["out"] < glob["out"]
            assert part["total"] < glob["total"]
        # and the engine-measured accounting agrees at G=2 vs G=1
        maj = m // 2 + 1
        pk_g, ow_g, nb_g = uniform_traffic(1, m, m, batch_nbytes=b)
        st_g, _ = stability_tick(init_dissem(1, m, m), jnp.asarray(pk_g),
                                 majority=maj)
        in_g, _ = per_node_bytes(st_g, ow_g, nb_g, m)
        mp = partition_size(m, 2)
        pk_p, ow_p, nb_p = uniform_traffic(2, mp, mp, batch_nbytes=b)
        st_p, _ = stability_tick(init_dissem(2, mp, mp), jnp.asarray(pk_p),
                                 majority=mp // 2 + 1)
        in_p, _ = per_node_bytes(st_p, ow_p, nb_p, mp)
        assert in_p.max() < in_g.max()


class TestAnalyticalPartitioned:
    def test_groups_1_is_exact_identity(self):
        base = bytes_ht_disseminator(3000, 12, 3, 100)
        assert bytes_ht_disseminator_partitioned(3000, 12, 3, 100, 1) == base

    def test_monotone_decreasing_in_groups(self):
        prev = bytes_ht_disseminator_partitioned(3000, 12, 3, 100, 1)
        for G in (2, 3, 4, 6, 12):
            cur = bytes_ht_disseminator_partitioned(3000, 12, 3, 100, G)
            assert cur["in"] < prev["in"]
            assert cur["total"] < prev["total"]
            prev = cur

    def test_ragged_partition_raises(self):
        with pytest.raises(ValueError):
            bytes_ht_disseminator_partitioned(3000, 12, 3, 100, 5)
