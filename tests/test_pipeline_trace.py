"""Stage scopes and boundary counters of the closed pipeline.

The compiled programs name every stage of ``repro.pipeline.STAGES`` in
their op metadata, and the per-tick counters of ``pipeline_tick`` agree
with bookkeeping done here from the pipeline's own state: requests with
the workload, ordered and decided ids with the merge log and the windows,
and the exact mean waits with each batch's admission and decision ticks.
"""
from __future__ import annotations

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.engine import adaptive as ad  # noqa: E402
from repro.engine.api import (EngineConfig, GatingConfig,  # noqa: E402
                              RecyclingConfig)
from repro.pipeline import (COUNTERS, STAGES, PipelineConfig,  # noqa: E402
                            WorkloadModel, build_route_table, committed,
                            init_pipeline, pipeline_tick_jit, run_pipeline)

C = 10
LAGS = dict(ack_lag=(0, 1, 1, 2, 2), hold_lag=(0, 0, 1, 1, 2),
            vote_lag=(1, 2, 2))


def make_cfg(family: str) -> PipelineConfig:
    recycling = None if family == "gated" else \
        RecyclingConfig(watermark=8, id_stride=4096)
    window = 256 if family == "gated" else 16
    adaptive = ad.AdaptiveConfig(max_tiles_per_tick=3, policy="unstable") \
        if family == "adaptive" else None
    return PipelineConfig(
        engine=EngineConfig(
            groups=2, window=window, n_diss=5, n_seq=3, order_budget=4,
            merge_capacity=2 * 2048, recycling=recycling,
            gating=GatingConfig(), adaptive=adaptive),
        n_clients=C, budget_bytes=2500, capacity=256, seq_capacity=64,
        **LAGS)


def op_names(compiled_text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


@pytest.mark.parametrize("family", ["gated_recycled", "adaptive", "gated"])
def test_compiled_programs_name_every_stage(family):
    cfg = make_cfg(family)
    st = init_pipeline(cfg)
    rt = jnp.asarray(build_route_table(cfg))
    rows = (jnp.zeros((4, C), bool), jnp.zeros((4, C), jnp.int32))
    names = op_names(run_pipeline.lower(cfg, st, *rows, rt).compile()
                     .as_text())
    names |= op_names(jax.jit(committed, static_argnums=0).lower(cfg, st)
                      .compile().as_text())
    scopes = {part for name in names for part in name.split("/")}
    # the single-use window of the gated family never recycles
    want = [s for s in STAGES
            if not (family == "gated" and s == "ht.recycle")]
    assert [s for s in want if s not in scopes] == []
    assert all(s.startswith("ht.") for s in STAGES)
    assert len(set(STAGES)) == len(STAGES) == 9


def run_ticks(cfg: PipelineConfig, seed: int, ticks: int, drain: int):
    """Tick by tick through ``ticks`` arrival ticks and ``drain`` ticks
    without arrivals; per tick the counters and, from the state, the ids
    ordered (in the merge log) and decided (retired, or decided in a
    window slot) by then."""
    wl = WorkloadModel(n_clients=C, arrival_rate=0.6,
                       size_choices=(100, 400, 1800)).draw(
                           jax.random.PRNGKey(seed), ticks)
    rt = jnp.asarray(build_route_table(cfg))
    st = init_pipeline(cfg)
    none = (jnp.zeros((C,), bool), jnp.zeros((C,), jnp.int32))
    outs, ordered_at, decided_at = [], {}, {}
    for t in range(ticks + drain):
        row = (wl.arrived[t], wl.sizes[t]) if t < ticks else none
        st, out = pipeline_tick_jit(cfg, st, *row, rt)
        outs.append({k: int(out[k]) for k in COUNTERS})
        core = st.engine.core
        if cfg.engine.recycling is not None:
            q, sids, retired = core.rs.q, core.rs.slot_ids, core.rs.retired
        else:
            q, sids, retired = core, st.engine.slot_ids, np.zeros(2, int)
        logs = np.asarray(st.engine.merge.logs)
        marks = np.asarray(st.engine.merge.watermarks)
        decided = set(np.asarray(sids)[np.asarray(q.decided)].tolist())
        for g in range(cfg.engine.groups):
            real = [e for e in logs[g, :marks[g]] if e >= 0]
            for e in real:
                ordered_at.setdefault(int(e), t)
            decided |= set(int(e) for e in real[:int(retired[g])])
        for e in decided:
            decided_at.setdefault(e, t)
    return wl, st, outs, ordered_at, decided_at


@pytest.mark.parametrize("family", ["gated_recycled", "adaptive", "gated"])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_counters_agree_with_the_state(family, seed):
    cfg = make_cfg(family)
    wl, st, outs, ordered_at, decided_at = run_ticks(cfg, seed, 24, 16)
    assert not bool(st.overflowed)
    cum = {k: np.cumsum([o[k] for o in outs]) for k in COUNTERS}
    assert cum["requests"][-1] == int(np.asarray(wl.arrived).sum()) > 0
    assert cum["admitted"][-1] == cum["flushed"][-1] \
        == int(st.n_flushed.sum())
    assert (cum["ordered"] <= cum["admitted"]).all()
    assert (cum["stable"] <= cum["admitted"]).all()
    assert (cum["decided"] <= cum["ordered"]).all()
    assert (cum["decided"] <= cum["stable"]).all()
    n = cum["admitted"][-1]
    assert cum["ordered"][-1] == cum["stable"][-1] == cum["decided"][-1] \
        == n
    _, _, com = committed(cfg, st)
    assert int(com) == n
    assert (cum["dropped"] == 0).all()
    # ids seen ordered / decided by tick t, counted from the state
    ticks = np.arange(len(outs))
    assert list(cum["ordered"]) == [
        sum(1 for x in ordered_at.values() if x <= t) for t in ticks]
    assert list(cum["decided"]) == [
        sum(1 for x in decided_at.values() if x <= t) for t in ticks]


@pytest.mark.parametrize("family", ["gated_recycled", "adaptive"])
def test_exact_waits_equal_per_batch_means(family):
    """Σₜ(cumulative admitted − cumulative ordered or decided) over the
    admitted batches is each batch's mean wait from its admission tick,
    exactly, once the drain has decided every batch."""
    cfg = make_cfg(family)
    _, st, outs, ordered_at, decided_at = run_ticks(cfg, 11, 24, 16)
    cum = {k: np.cumsum([o[k] for o in outs]) for k in COUNTERS}
    n = int(cum["admitted"][-1])
    assert n == len(ordered_at) == len(decided_at) > 0
    stride = cfg.id_stride
    admit_tick = np.asarray(st.admit_tick)

    def mean_wait(done_at):
        return sum(t - int(admit_tick[i // stride, i % stride])
                   for i, t in done_at.items()) / n

    order_wait = (cum["admitted"] - cum["ordered"]).sum() / n
    decide_wait = (cum["admitted"] - cum["decided"]).sum() / n
    assert order_wait == mean_wait(ordered_at)
    assert decide_wait == mean_wait(decided_at)
    assert 0 < order_wait < decide_wait


# -- the meshed engine (subprocess: XLA_FLAGS before jax starts) --------------

_MESHED_CHILD = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import adaptive as ad
from repro.engine.api import (EngineConfig, GatingConfig, MeshConfig,
                              RecyclingConfig)
from repro.pipeline import (COUNTERS, PipelineConfig, WorkloadModel,
                            build_route_table, committed, init_pipeline,
                            run_pipeline)

C, T, DRAIN = 10, 24, 16
out = {"devices": len(jax.devices())}
for family in ("gated_recycled", "adaptive"):
    adaptive = ad.AdaptiveConfig(max_tiles_per_tick=3, policy="unstable") \
        if family == "adaptive" else None
    runs = []
    for mesh in (None, MeshConfig()):
        cfg = PipelineConfig(
            engine=EngineConfig(
                groups=2, window=16, n_diss=5, n_seq=3, order_budget=4,
                merge_capacity=2 * 2048,
                recycling=RecyclingConfig(watermark=8, id_stride=4096),
                gating=GatingConfig(), adaptive=adaptive, mesh=mesh),
            n_clients=C, budget_bytes=2500, capacity=256, seq_capacity=64,
            ack_lag=(0, 1, 1, 2, 2), hold_lag=(0, 0, 1, 1, 2),
            vote_lag=(1, 2, 2))
        wl = WorkloadModel(n_clients=C, arrival_rate=0.6,
                           size_choices=(100, 400, 1800)).draw(
                               jax.random.PRNGKey(7), T)
        arrived = jnp.concatenate([wl.arrived, jnp.zeros((DRAIN, C), bool)])
        sizes = jnp.concatenate([wl.sizes,
                                 jnp.zeros((DRAIN, C), jnp.int32)])
        st, counts = run_pipeline(cfg, init_pipeline(cfg), arrived, sizes,
                                  jnp.asarray(build_route_table(cfg)))
        merged, n, com = committed(cfg, st)
        runs.append({"counts": {k: np.asarray(counts[k]).tolist()
                                for k in COUNTERS},
                     "merged": np.asarray(merged[:int(n)]).tolist(),
                     "committed": int(com),
                     "arrivals": int(np.asarray(wl.arrived).sum())})
    out[family] = runs
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def meshed_runs():
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    src = Path(__file__).resolve().parent.parent / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(src) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _MESHED_CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.parametrize("family", ["gated_recycled", "adaptive"])
def test_meshed_counters_equal_one_device(meshed_runs, family):
    """On a two-device group mesh the counters, and the committed log,
    are those of the same pipeline on one device."""
    assert meshed_runs["devices"] == 2
    one, mesh = meshed_runs[family]
    assert mesh == one
    cum = {k: np.cumsum(v) for k, v in one["counts"].items()}
    assert cum["requests"][-1] == one["arrivals"] > 0
    n = cum["admitted"][-1]
    assert cum["decided"][-1] == cum["stable"][-1] == n == one["committed"]
