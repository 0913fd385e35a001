"""The system under test: the closed HT-Paxos pipeline of ``src/repro``.

Only public entry points are driven: ``init_pipeline``, ``run_pipeline``
(a chunk of ticks), ``pipeline_tick_jit`` (one tick), ``committed`` (the
merge and commit gate) and ``build_route_table``. The deployment file sets
every size and the per-node delays (``node_lags``).

A deployment may ask for the group-sharded engine with ``mesh_devices``
(an int): the engine's G groups are then split over that many devices,
G / ``mesh_devices`` to each (``EngineConfig(mesh=MeshConfig(n_devices=
mesh_devices))``, ``repro.engine.meshed``). Without the key the engine runs
on one device. ``Program`` refuses a deployment whose ``mesh_devices`` JAX
cannot give it or that does not divide ``groups``, where the program's own
mesh would quietly use fewer devices or pad the groups. The jitted entry
points get the same arguments on both paths: the route table, the empty
row and the traffic rows stay uncommitted, on the default device, and
``jit`` places them on the mesh as the sharded program needs.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp


def node_lags(dep: dict) -> list[dict]:
    """Per-node message delays in ticks, one dict of ``ack`` / ``hold`` /
    ``vote`` arrays for each of the deployment's ``delay_profiles``. In a
    profile, each role's stated range ``[lo, hi]`` is dealt to the nodes in
    turn (node j gets ``lo + j mod (hi - lo + 1)``), so each value has an
    equal share as near as the node count allows. The delays are a property
    of the deployment, not of the seed: they are compiled into the program,
    and a seed that changed them would compile it anew."""
    counts = {"ack": dep["disseminators"],
              "hold": dep["disseminators"] // dep["groups"],
              "vote": dep["sequencers"]}
    out = []
    for profile in dep["delay_profiles"]:
        lags = {}
        for role, n in counts.items():
            lo, hi = profile[role]
            lags[role] = lo + np.arange(n) % (hi - lo + 1)
        out.append(lags)
    return out


def mesh_devices(dep: dict) -> int | None:
    """The deployment's ``mesh_devices``, or None without the key; a value
    that is not a whole number from 1 up dividing ``groups`` is an error."""
    n = dep.get("mesh_devices")
    if n is None:
        return None
    if isinstance(n, bool) or not isinstance(n, int) or n < 1:
        raise ValueError(f"mesh_devices must be a whole number >= 1, "
                         f"got {n!r}")
    if dep["groups"] % n:
        raise ValueError(f"mesh_devices={n} does not divide groups="
                         f"{dep['groups']}: every device holds as many "
                         f"groups")
    return n


def pipeline_config(dep: dict, lags: dict, gate_open: bool = False):
    """The program's configuration of the deployment under one delay
    profile. ``gate_open`` seeds every id stable, which switches the
    stability gate off (the program's ungated path; a fault here)."""
    from repro.engine.api import (EngineConfig, GatingConfig, MeshConfig,
                                  RecyclingConfig)
    from repro.pipeline import PipelineConfig
    ticks = dep["segment_ticks"] + dep["drain_ticks_max"]
    n_mesh = mesh_devices(dep)
    return PipelineConfig(
        engine=EngineConfig(
            groups=dep["groups"], window=dep["window"],
            n_diss=dep["disseminators"], n_seq=dep["sequencers"],
            order_budget=dep["order_budget"],
            merge_capacity=ticks * dep["order_budget"],
            recycling=RecyclingConfig(watermark=dep["recycle_watermark"],
                                      id_stride=dep["admission_capacity"]),
            gating=GatingConfig(
                n_diss_partition=dep["disseminators"] // dep["groups"],
                pre_stable=gate_open, fresh_stable=gate_open),
            mesh=None if n_mesh is None else MeshConfig(n_devices=n_mesh)),
        n_clients=dep["clients"], budget_bytes=dep["batch_budget_bytes"],
        ack_lag=tuple(int(x) for x in lags["ack"]),
        hold_lag=tuple(int(x) for x in lags["hold"]),
        vote_lag=tuple(int(x) for x in lags["vote"]),
        capacity=dep["admission_capacity"],
        seq_capacity=dep["seq_capacity"])


def route_table(cfg, cache_dir: Path | None) -> np.ndarray:
    """``build_route_table(cfg)``, kept as a file keyed by its shape."""
    from repro.pipeline import build_route_table
    if cache_dir is None:
        return build_route_table(cfg)
    path = Path(cache_dir) / (f"route_D{cfg.n_lanes}_S{cfg.seq_capacity}"
                              f"_G{cfg.engine.groups}.npy")
    if path.exists():
        return np.load(path)
    table = build_route_table(cfg)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npy")
    np.save(tmp, table)
    tmp.replace(path)
    return table


class Program:
    """One deployment of the pipeline, with the calls a run makes.

    Log segment ``k`` runs under delay profile ``k mod len(profiles)``:
    ``init(k)`` starts it, and the tick calls that follow use that
    profile's compiled programs. The profiles differ only in the delays,
    so every segment shares one route table and one commit gate."""

    gate_open = False

    def __init__(self, dep: dict, profiles: list[dict],
                 cache_dir: Path | None):
        from repro.pipeline import (committed, init_pipeline,
                                    pipeline_tick_jit, run_pipeline)
        n_mesh = mesh_devices(dep)
        if n_mesh is not None and n_mesh > len(jax.devices()):
            raise ValueError(f"mesh_devices={n_mesh} but JAX sees "
                             f"{len(jax.devices())} device(s)")
        self.cfgs = [pipeline_config(dep, lags, self.gate_open)
                     for lags in profiles]
        if len({c.engine for c in self.cfgs}) != 1:
            raise ValueError("delay profiles must share the engine")
        self.cfg = self.cfgs[0]
        self.route = jnp.asarray(route_table(self.cfg, cache_dir))
        C = self.cfg.n_clients
        self.no_arrivals = (jnp.zeros((C,), jnp.bool_),
                            jnp.zeros((C,), jnp.int32))
        self._init = jax.jit(init_pipeline, static_argnums=0)
        self._committed = jax.jit(committed, static_argnums=0)
        self._tick = pipeline_tick_jit
        self._run = run_pipeline

    def init(self, segment: int):
        """Fresh state for log segment ``segment``; selects its profile."""
        self.cfg = self.cfgs[segment % len(self.cfgs)]
        return self._init(self.cfgs[0])

    def run_chunk(self, state, arrived, sizes):
        """``run_pipeline`` over rows bool[T, C] / int32[T, C]; returns
        (state, per-tick admitted int32[T])."""
        state, outs = self._run(self.cfg, state, arrived, sizes, self.route)
        return state, outs["admitted"]

    def tick(self, state, arrived, sizes):
        """``pipeline_tick_jit`` on one row; returns (state, admitted)."""
        state, out = self._tick(self.cfg, state, arrived, sizes, self.route)
        return state, out["admitted"]

    def committed(self, state):
        """(merged int32[G·L], merged count, committed count): the merge
        and commit gate read only the engine, which every profile shares."""
        return self._committed(self.cfgs[0], state)

    @staticmethod
    def admitted(state):
        return state.admit_count.sum()

    def record(self, state, merged, com) -> dict:
        """What the segment produced, still on the device: the committed
        log and the admission and wire records."""
        m = state.engine.merge
        return {"merged": merged, "committed": com,
                "admit_count": state.admit_count,
                "admit_tick": state.admit_tick,
                "bid_code": state.bid_code,
                "n_flushed": state.n_flushed,
                "flushed_bytes": state.flushed_bytes,
                "overflowed": state.overflowed,
                "merge_overflowed": m.overflowed.sum()}
