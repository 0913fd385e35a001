"""Engine facade (repro.engine.api): bit-parity with the legacy
families, EngineConfig validation, and the deprecation layer.

Parity is by delegation, so these tests pin the *wiring*: for every
EngineConfig cell (plain / recycled / gated / gated_recycled), the
facade's ``run``/``tick`` must produce bit-identical merged logs,
counts, commit gates and final core state to the legacy per-family
call spelled out by hand with the same traffic. Traffic fixtures follow
``tests/test_window_recycling.py`` / ``tests/test_engine_sharded.py``
(random packed tiles, saturated holds)."""
from __future__ import annotations

import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import repro.engine  # noqa: E402
from repro.engine import merge as M  # noqa: E402
from repro.engine import sharded as S  # noqa: E402
from repro.engine import api  # noqa: E402
from repro.engine.api import (Engine, EngineConfig, EngineState,  # noqa: E402
                              GatingConfig, RecyclingConfig)
from repro.engine.epochs import EpochTable  # noqa: E402
from repro.dissem.engine import init_dissem  # noqa: E402

G, W, D, SQ, B, T = 2, 16, 5, 3, 4, 12
DM, SM, STAB = 3, 2, 3
STRIDE = 4096


def tiles(seed, *, holds=False):
    rng = np.random.default_rng(seed)
    acks = (rng.random((T, G, W, 1)) < 0.7) * np.uint32(0x1F)
    votes = (rng.random((T, G, W, 1)) < 0.6) * np.uint32(0x7)
    out = [jnp.asarray(acks), jnp.asarray(votes)]
    if holds:
        h = (rng.random((T, G, W, 1)) < 0.8) * np.uint32(0x1F)
        out.append(jnp.asarray(h))
    return out


def assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# run() parity, one test per family
# ---------------------------------------------------------------------------

def test_run_parity_plain():
    acks, votes = tiles(0)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       diss_majority=DM, seq_majority=SM)
    assert cfg.family == "plain"
    stf, merged_f, cnt_f, com_f = api.run(cfg, api.create_state(cfg),
                                          acks, votes)
    st = S.init_sharded(G, W, D, SQ)
    sids = S.default_slot_ids(G, W)
    st, ms, merged_l, cnt_l, com_l = S.run_sharded_ticks_merged(
        st, M.init_merge(G, T * B), acks, votes, sids,
        diss_majority=DM, seq_majority=SM, order_budget=B)
    assert int(cnt_f) == int(cnt_l) and int(com_f) == int(com_l)
    assert np.array_equal(np.asarray(merged_f), np.asarray(merged_l))
    assert_trees_equal(stf.core, st)
    assert_trees_equal(stf.merge, ms)
    assert int(cnt_f) > 0      # fixture actually ordered something


def test_run_parity_recycled():
    acks, votes = tiles(1)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       diss_majority=DM, seq_majority=SM,
                       recycling=RecyclingConfig(watermark=W // 2,
                                                 id_stride=STRIDE))
    assert cfg.family == "recycled"
    stf, merged_f, cnt_f, com_f = api.run(cfg, api.create_state(cfg),
                                          acks, votes)
    rs, ms, merged_l, cnt_l, com_l = S.run_recycled_ticks_merged(
        S.init_recycled(G, W, D, SQ, id_stride=STRIDE),
        M.init_merge(G, T * B), acks, votes,
        diss_majority=DM, seq_majority=SM, order_budget=B,
        watermark=W // 2, id_stride=STRIDE)
    assert int(cnt_f) == int(cnt_l) and int(com_f) == int(com_l)
    assert np.array_equal(np.asarray(merged_f), np.asarray(merged_l))
    assert_trees_equal(stf.core, rs)
    assert int(cnt_f) > 0


def test_run_parity_gated():
    acks, votes, holds = tiles(2, holds=True)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       diss_majority=DM, seq_majority=SM,
                       gating=GatingConfig(stab_majority=STAB))
    assert cfg.family == "gated"
    stf, merged_f, cnt_f, com_f = api.run(cfg, api.create_state(cfg),
                                          acks, votes, holds)
    st = S.init_sharded(G, W, D, SQ)
    d = init_dissem(G, W, D)
    sids = S.default_slot_ids(G, W)
    st, d, ms, merged_l, cnt_l, com_l = S.run_gated_ticks_merged(
        st, d, M.init_merge(G, T * B), acks, holds, votes, sids,
        diss_majority=DM, seq_majority=SM, stab_majority=STAB,
        order_budget=B)
    assert int(cnt_f) == int(cnt_l) and int(com_f) == int(com_l)
    assert np.array_equal(np.asarray(merged_f), np.asarray(merged_l))
    assert_trees_equal(stf.core, st)
    assert_trees_equal(stf.dissem, d)
    assert int(cnt_f) > 0


def test_run_parity_gated_recycled():
    acks, votes, holds = tiles(3, holds=True)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       diss_majority=DM, seq_majority=SM,
                       recycling=RecyclingConfig(watermark=W // 2,
                                                 id_stride=STRIDE),
                       gating=GatingConfig(stab_majority=STAB))
    assert cfg.family == "gated_recycled"
    stf, merged_f, cnt_f, com_f = api.run(cfg, api.create_state(cfg),
                                          acks, votes, holds)
    gs, ms, merged_l, cnt_l, com_l = S.run_gated_recycled_ticks_merged(
        S.init_gated_recycled(G, W, D, SQ, n_diss_partition=D,
                              id_stride=STRIDE),
        M.init_merge(G, T * B), acks, holds, votes,
        diss_majority=DM, seq_majority=SM, stab_majority=STAB,
        order_budget=B, watermark=W // 2, id_stride=STRIDE)
    assert int(cnt_f) == int(cnt_l) and int(com_f) == int(com_l)
    assert np.array_equal(np.asarray(merged_f), np.asarray(merged_l))
    assert_trees_equal(stf.core, gs)
    assert int(cnt_f) > 0


# ---------------------------------------------------------------------------
# tick()/recycle()/committed_prefix parity & Engine object behavior
# ---------------------------------------------------------------------------

def test_tick_loop_equals_run_gated_recycled():
    acks, votes, holds = tiles(4, holds=True)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       diss_majority=DM, seq_majority=SM,
                       recycling=RecyclingConfig(watermark=W // 2,
                                                 id_stride=STRIDE),
                       gating=GatingConfig(stab_majority=STAB))
    st_run, merged_r, cnt_r, com_r = api.run(cfg, api.create_state(cfg),
                                             acks, votes, holds)
    st = api.create_state(cfg)
    for t in range(T):
        st, out = api.tick(cfg, st, acks[t], votes[t], holds[t])
        assert int(out["dropped"]) == 0
    merged_t, cnt_t, com_t = api.committed_prefix(cfg, st)
    assert int(cnt_t) == int(cnt_r) and int(com_t) == int(com_r)
    assert np.array_equal(np.asarray(merged_t)[:int(cnt_t)],
                          np.asarray(merged_r)[:int(cnt_r)])
    assert_trees_equal(st.core, st_run.core)


def test_engine_object_matches_functional():
    acks, votes, holds = tiles(5, holds=True)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       gating=GatingConfig(stab_majority=STAB))
    eng = Engine.create(cfg)
    for t in range(T):
        eng.tick(acks[t], votes[t], holds[t])
    st = api.create_state(cfg)
    for t in range(T):
        st, _ = api.tick(cfg, st, acks[t], votes[t], holds[t])
    assert_trees_equal(eng.state, st)
    m1, c1, k1 = eng.committed()
    m2, c2, k2 = api.committed_prefix(cfg, st)
    assert int(c1) == int(c2) and int(k1) == int(k2)
    assert np.array_equal(np.asarray(eng.slot_ids),
                          np.asarray(api.slot_ids(st)))
    assert "gated" in repr(eng)


def test_recycle_facade_matches_legacy():
    acks, votes = tiles(6)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       recycling=RecyclingConfig(watermark=W,
                                                 id_stride=STRIDE))
    st = api.create_state(cfg)
    for t in range(T):
        st, _ = api.tick(cfg, st, acks[t], votes[t])
    st2, n2 = api.recycle(cfg, st)
    rs_l, n_l = S.recycle_groups(st.core, watermark=W, id_stride=STRIDE)
    assert np.array_equal(np.asarray(n2), np.asarray(n_l))
    assert_trees_equal(st2.core, rs_l)


def test_reconfigure_facade_matches_legacy():
    from repro.engine import epochs as EP
    table = EpochTable(((0, 1), (0,)), n_rows=G)
    acks, votes = tiles(7)
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=4 * T * B,
                       diss_majority=DM, seq_majority=SM,
                       recycling=RecyclingConfig(watermark=W // 2,
                                                 id_stride=STRIDE),
                       epochs=table)
    eng = Engine.create(cfg)
    eng.run(acks, votes)
    # drain: saturate until quiescent, mirroring the membership bench
    za = jnp.full((G, W, 1), 0xFFFFFFFF, jnp.uint32)
    zv = jnp.full((G, W, 1), 0xFFFFFFFF, jnp.uint32)
    for _ in range(32):
        if EP.is_drained(eng.state.core.q):
            break
        eng.tick(za, zv)
    st_before = eng.state
    report = eng.reconfigure(1)
    core_l, ms_l, report_l = EP.reconfigure_recycled(
        st_before.core, st_before.merge, table, 0, 1, id_stride=STRIDE)
    assert report["epoch"] == report_l["epoch"] == 1
    assert report["moved"] == report_l["moved"]
    assert_trees_equal(eng.state.core, core_l)
    assert_trees_equal(eng.state.merge, ms_l)
    assert eng.epoch == 1


# ---------------------------------------------------------------------------
# EngineConfig validation (satellite: kwargs normalized at create time)
# ---------------------------------------------------------------------------

def base_kw(**over):
    kw = dict(groups=G, window=W, n_diss=D, n_seq=SQ, order_budget=B,
              merge_capacity=64)
    kw.update(over)
    return kw


def test_config_defaults_normalized():
    cfg = EngineConfig(**base_kw())
    assert cfg.diss_majority == D // 2 + 1
    assert cfg.seq_majority == SQ // 2 + 1
    assert cfg.max_entries == B
    cfg = EngineConfig(**base_kw(groups=1,
                                 recycling=RecyclingConfig(watermark=4)))
    assert cfg.recycling.id_stride == W      # single group: defaults to W
    cfg = EngineConfig(**base_kw(gating=GatingConfig()))
    assert cfg.gating.n_diss_partition == D
    assert cfg.gating.stab_majority == D // 2 + 1


@pytest.mark.parametrize("kw,match", [
    (dict(window=0), "window"),
    (dict(order_budget=0), "order_budget"),
    (dict(diss_majority=D + 1), "diss_majority"),
    (dict(seq_majority=0), "seq_majority"),
    (dict(max_entries=B - 1), "max_entries"),
    (dict(recycling=RecyclingConfig(watermark=0, id_stride=STRIDE)),
     "watermark"),
    (dict(recycling=RecyclingConfig(watermark=4)), "id_stride"),
    (dict(recycling=RecyclingConfig(watermark=4, id_stride=W - 1)),
     "id_stride"),
    (dict(gating=GatingConfig(stab_majority=D + 1)), "stab_majority"),
    (dict(gating=GatingConfig(n_diss_partition=0)), "n_diss_partition"),
    (dict(epochs=EpochTable(((0,),), n_rows=1)), "n_rows"),
])
def test_config_rejects_inconsistencies(kw, match):
    with pytest.raises(ValueError, match=match):
        EngineConfig(**base_kw(**kw))


def test_holds_required_iff_gated():
    acks, votes, holds = tiles(8, holds=True)
    plain = EngineConfig(**base_kw())
    gated = EngineConfig(**base_kw(gating=GatingConfig()))
    with pytest.raises(ValueError, match="hold"):
        api.tick(plain, api.create_state(plain), acks[0], votes[0],
                 holds[0])
    with pytest.raises(ValueError, match="hold"):
        api.tick(gated, api.create_state(gated), acks[0], votes[0])


def test_reconfigure_requires_epochs_and_rejects_gated_window():
    cfg = EngineConfig(**base_kw())
    with pytest.raises(ValueError, match="epochs"):
        api.reconfigure(cfg, api.create_state(cfg), 0, 1)
    cfg = EngineConfig(**base_kw(gating=GatingConfig(),
                                 epochs=EpochTable(((0, 1), (0,)),
                                                   n_rows=G)))
    with pytest.raises(ValueError, match="recycl"):
        api.reconfigure(cfg, api.create_state(cfg), 0, 1)
    with pytest.raises(ValueError, match="epoch"):
        Engine.create(cfg, epoch=5)


def test_recycle_requires_recycling():
    cfg = EngineConfig(**base_kw())
    with pytest.raises(ValueError, match="recycl"):
        api.recycle(cfg, api.create_state(cfg))


def test_config_is_hashable_static_arg():
    a = EngineConfig(**base_kw(gating=GatingConfig()))
    b = EngineConfig(**base_kw(gating=GatingConfig()))
    assert a == b and hash(a) == hash(b)
    assert a != EngineConfig(**base_kw())


# ---------------------------------------------------------------------------
# deprecation layer
# ---------------------------------------------------------------------------

def test_package_level_legacy_access_warns():
    with pytest.warns(DeprecationWarning, match="Engine.create"):
        repro.engine.init_sharded
    with pytest.warns(DeprecationWarning, match="Engine.run"):
        repro.engine.run_gated_recycled_ticks_merged
    with pytest.warns(DeprecationWarning, match="Engine.reconfigure"):
        repro.engine.reconfigure_recycled


def test_submodule_and_facade_access_stay_clean():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        from repro.engine import sharded as s2
        s2.init_sharded(1, 4, 3, 3)                  # defining module: clean
        repro.engine.Engine                           # facade names: clean
        repro.engine.EngineConfig
        repro.engine.init_merge(1, 8)                 # non-family helper
        repro.engine.default_slot_ids(1, 4)


def test_facade_types_importable_from_package():
    assert repro.engine.Engine is Engine
    assert repro.engine.EngineConfig is EngineConfig
    assert repro.engine.EngineState is EngineState


@pytest.mark.parametrize("family", ["plain", "recycled", "gated",
                                    "gated_recycled"])
def test_progress_counts_the_merge_log(family):
    """``api.progress`` of every family: ``ordered`` is the ids in the
    merge log, ``decided`` lies between the committed prefix and
    ``ordered``, and only gated families count ``stable``."""
    gated = family.startswith("gated")
    recycling = RecyclingConfig(watermark=4, id_stride=STRIDE) \
        if "recycled" in family else None
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=B, merge_capacity=T * B,
                       diss_majority=DM, seq_majority=SM,
                       recycling=recycling,
                       gating=GatingConfig(stab_majority=STAB)
                       if gated else None)
    assert cfg.family == family
    st = api.create_state(cfg)
    assert {k: int(v) for k, v in api.progress(cfg, st).items()} == \
        dict(ordered=0, decided=0, **({"stable": 0} if gated else {}))
    st, merged, count, com = api.run(cfg, st, *tiles(5, holds=gated))
    got = {k: int(v) for k, v in api.progress(cfg, st).items()}
    assert set(got) == {"ordered", "decided"} | ({"stable"} if gated
                                                 else set())
    real = np.asarray(merged)[:int(count)] >= 0
    assert got["ordered"] == int(real.sum()) > 0
    assert int(real[:int(com)].sum()) <= got["decided"] <= got["ordered"]
    if gated:
        assert got["decided"] <= got["stable"]
