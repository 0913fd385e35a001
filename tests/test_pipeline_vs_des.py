"""Closed-pipeline cross-validation: jax pipeline vs DES, shared workload.

The strongest cross-check in the suite: the SAME pre-drawn workload
arrays drive both the closed in-jax pipeline and ``HTPaxosSim``, and
both must produce the identical learner batch order. The alignment
construction that makes bit-equality provable lives in
:mod:`repro.pipeline.crosscheck`, where ``chip_smoke.py`` reuses it.
"""
from __future__ import annotations

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.engine.epochs import EpochTable  # noqa: E402
from repro.pipeline import (build_route_table, committed,  # noqa: E402
                            decode_merged, init_pipeline, run_pipeline,
                            reconfigure_pipeline)
from repro.pipeline.crosscheck import (P, cover_pipeline_config,  # noqa: E402
                                       cover_workload, drain,
                                       greedy_cover_schedule,
                                       pipeline_vs_des, run_des)


@pytest.mark.parametrize("G,D", [(1, 5), (2, 10), (4, 12)])
def test_closed_pipeline_matches_des(G, D):
    r = pipeline_vs_des(G, D)
    assert not r["overflowed"]
    assert r["dropped"] == 0
    assert r["admitted"] == len(r["plan"])
    assert r["committed"] == r["admitted"], "pipeline failed to drain"
    assert len(r["des_order"]) == len(r["plan"])
    assert r["jax_order"] == r["des_order"]


def test_closed_pipeline_matches_des_reconfig():
    """G=2, epoch 0 active (0, 1) → epoch 1 active (0,), switched at a
    quiescent cycle boundary on both sides."""
    G, D, k0, k1 = 2, 10, 6, 6
    n_cycles = k0 + k1
    table = EpochTable(((0, 1), (0,)), n_rows=G)
    plan = greedy_cover_schedule(
        D, [(0, 1)] * k0 + [(0,)] * k1, [0] * k0 + [1] * k1, table)
    wl = cover_workload(plan, n_cycles, D, 2 * D)

    pcfg = cover_pipeline_config(G, D, table=table)
    rt0 = jnp.asarray(build_route_table(pcfg, epoch=0))
    rt1 = jnp.asarray(build_route_table(pcfg, epoch=1))
    st = init_pipeline(pcfg)
    st, o1 = run_pipeline(pcfg, st, wl.arrived[:k0], wl.sizes[:k0], rt0)
    st = drain(pcfg, st, rt0)
    st, report = reconfigure_pipeline(pcfg, st, 0, 1)
    assert int(report.get("moved", 0)) == 0
    st, o2 = run_pipeline(pcfg, st, wl.arrived[k0:], wl.sizes[k0:], rt1)
    st = drain(pcfg, st, rt1)
    assert not bool(st.overflowed)
    assert int(o1["dropped"].sum()) == 0 and int(o2["dropped"].sum()) == 0
    merged, count, com = committed(pcfg, st)
    n_adm = int(st.admit_count.sum())
    assert n_adm == len(plan)
    assert int(com) == n_adm, "pipeline failed to drain"
    jax_order = decode_merged(pcfg, st, merged, com)

    # DES: admin switch 2.5 after the skip fire that follows the last
    # epoch-0 decide — quiescent, matching the drained engine switch
    t_r = k0 * P + 2.5
    _, des_order = run_des(
        G, D, wl, reconfig=((t_r, (0,)),), until=n_cycles * P + 20)
    assert len(des_order) == len(plan)
    assert jax_order == des_order

    # epoch pinning really split the routing: some epoch-0 batch routed
    # to row 1, no epoch-1 batch did
    assert any(g == 1 for (_c, _d, _s, g) in plan[:k0 * G])
    assert all(g == 0 for (*_x, g) in plan[k0 * G:])
