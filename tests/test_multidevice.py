"""Device-sharded engine (repro.engine.meshed): 1-device ≡ N-device
bit-identity, the facade's MeshConfig wiring, and mesh parity for every
entry point.

Two layers:

* **In-process parity** — on whatever backend pytest runs under (1 CPU
  device in plain tier-1, 8 emulated devices in the CI
  ``--xla_force_host_platform_device_count=8`` leg), every meshed entry
  point (``api.run``, ``api.tick``, ``adaptive_pass``, ``subtick_pass``)
  must produce bit-identical merged logs, commit gates and core state to
  its unmeshed twin on the same traffic, for all four families.
* **Cross-device bit-identity** — one subprocess per device count
  (``XLA_FLAGS`` must be set before jax initializes its backend) runs a
  deterministic scenario set: all four families through fused runs deep
  enough to trigger **mid-run recycles** (fresh ids minted from
  per-group ranges — exactly what a wrong shard-local id base corrupts),
  a padded mesh (G not divisible by the device count), and a live
  **epoch reconfiguration** (drain-then-switch on sharded state). The
  parent asserts the full JSON output — merged learner prefixes
  included — is equal at 1 and 8 devices.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import jaxsim  # noqa: E402
from repro.engine import adaptive as AD  # noqa: E402
from repro.engine import api  # noqa: E402
from repro.engine.api import (EngineConfig, GatingConfig,  # noqa: E402
                              MeshConfig, RecyclingConfig)

G, W, D, SQ, T = 4, 16, 5, 3, 6
STRIDE = 1 << 16

FAMILY_KW = {
    "plain": {},
    "gated": dict(gating=GatingConfig()),
    "recycled": dict(recycling=RecyclingConfig(watermark=4,
                                               id_stride=STRIDE)),
    "gated_recycled": dict(recycling=RecyclingConfig(watermark=4,
                                                     id_stride=STRIDE),
                           gating=GatingConfig()),
}


def tiles(seed, words_n, *, t=T, g=G, density=0.7):
    rng = np.random.default_rng(seed)
    bits = rng.random((t, g, W, words_n)) < density
    return jax.vmap(jax.vmap(jaxsim.pack_tile))(jnp.asarray(bits))


def cfg_pair(fam, **extra):
    kw = dict(groups=G, window=W, n_diss=D, n_seq=SQ, order_budget=4,
              merge_capacity=4096, **FAMILY_KW[fam], **extra)
    return EngineConfig(**kw), EngineConfig(**kw, mesh=MeshConfig())


def traffic_for(cfg, seed=0):
    acks = tiles(seed, D)
    votes = tiles(seed + 1, SQ, density=0.6)
    holds = tiles(seed + 2, cfg.gating.n_diss_partition, density=0.9) \
        if cfg.gating else None
    return acks, votes, holds


def tree_eq(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(bool(jnp.array_equal(x, y))
                            for x, y in zip(la, lb))


@pytest.mark.parametrize("fam", sorted(FAMILY_KW))
def test_meshed_run_parity(fam):
    base, mesh = cfg_pair(fam)
    acks, votes, holds = traffic_for(base)
    _, m0, c0, k0 = api.run(base, api.create_state(base), acks, votes,
                            holds)
    st, m1, c1, k1 = api.run(mesh, api.create_state(mesh), acks, votes,
                             holds)
    assert int(c0) == int(c1) and int(k0) == int(k1)
    assert jnp.array_equal(m0, m1)
    # the returned state is logical-G, facade-shaped (pad sliced off)
    assert jax.tree_util.tree_leaves(st.core)[0].shape[0] == G


@pytest.mark.parametrize("fam", sorted(FAMILY_KW))
def test_meshed_tick_parity(fam):
    base, mesh = cfg_pair(fam)
    acks, votes, holds = traffic_for(base, seed=10)
    stb, stm = api.create_state(base), api.create_state(mesh)
    for t in range(T):
        h = None if holds is None else holds[t]
        stb, outb = api.tick(base, stb, acks[t], votes[t], h)
        stm, outm = api.tick(mesh, stm, acks[t], votes[t], h)
        assert jnp.array_equal(outb["assigned"], outm["assigned"]), t
    assert tree_eq(stb.core, stm.core)
    assert tree_eq(stb.merge, stm.merge)


def test_meshed_adaptive_pass_parity():
    kw = dict(groups=G, window=W, n_diss=D, n_seq=SQ, order_budget=4,
              merge_capacity=4096,
              recycling=RecyclingConfig(watermark=4, id_stride=STRIDE),
              adaptive=AD.AdaptiveConfig(max_tiles_per_tick=3,
                                         policy="backlog"))
    base = EngineConfig(**kw)
    mesh = EngineConfig(**kw, mesh=MeshConfig())
    acks, votes = tiles(20, D, t=8), tiles(21, SQ, t=8, density=0.6)
    lengths = jnp.asarray([8, 2, 5, 1], jnp.int32)
    stb = api.create_state(base)
    stm = api.create_state(mesh)
    qb = AD.queue_from_arrays(base, acks, votes, lengths=lengths)
    qm = AD.queue_from_arrays(mesh, acks, votes, lengths=lengths)
    for i in range(5):
        stb, qb, outb = AD.adaptive_pass(base, stb, qb)
        stm, qm, outm = AD.adaptive_pass(mesh, stm, qm)
        assert int(outb["rounds"]) == int(outm["rounds"]), i
        assert jnp.array_equal(outb["consumed"], outm["consumed"]), i
    assert tree_eq(stb.core, stm.core)
    assert jnp.array_equal(qb.head, qm.head)
    mb, cb, kb = api.committed_prefix(base, stb)
    mm, cm, km = api.committed_prefix(mesh, stm)
    assert jnp.array_equal(mb, mm) and int(cb) == int(cm)
    assert int(kb) == int(km)


def test_meshed_subtick_pass_parity():
    kw = dict(groups=G, window=W, n_diss=D, n_seq=SQ, order_budget=4,
              merge_capacity=4096,
              recycling=RecyclingConfig(watermark=4, id_stride=STRIDE),
              gating=GatingConfig(),
              adaptive=AD.AdaptiveConfig(max_tiles_per_tick=2,
                                         policy="undecided"))
    base = EngineConfig(**kw)
    mesh = EngineConfig(**kw, mesh=MeshConfig())
    part = base.gating.n_diss_partition
    stb, stm = api.create_state(base), api.create_state(mesh)
    for t in range(8):
        a = tiles(30 + t, D, t=1)[0]
        v = tiles(60 + t, SQ, t=1, density=0.6)[0]
        h = tiles(90 + t, part, t=1, density=0.9)[0]
        stb, outb = AD.subtick_pass(base, stb, a, v, h)
        stm, outm = AD.subtick_pass(mesh, stm, a, v, h)
        assert int(outb["rounds"]) == int(outm["rounds"]), t
    assert tree_eq(stb.core, stm.core)
    assert tree_eq(stb.merge, stm.merge)


def test_mesh_config_validation():
    kw = dict(groups=G, window=W, n_diss=D, n_seq=SQ, order_budget=4,
              merge_capacity=256)
    with pytest.raises(ValueError):
        EngineConfig(**kw, mesh=MeshConfig(n_devices=0))
    with pytest.raises(ValueError):
        EngineConfig(**kw, mesh="group")  # not a MeshConfig
    # n_devices beyond the host topology clamps instead of failing
    cfg = EngineConfig(**kw, mesh=MeshConfig(n_devices=64))
    acks, votes, _ = traffic_for(cfg)
    _, _, c, _ = api.run(cfg, api.create_state(cfg), acks, votes)
    base = EngineConfig(**kw)
    _, _, c0, _ = api.run(base, api.create_state(base), acks, votes)
    assert int(c) == int(c0)


# -- cross-device bit-identity (subprocess per device count) ------------------

_CHILD = r"""
import json
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import jaxsim
from repro.engine import api
from repro.engine import epochs as EP
from repro.engine.api import (Engine, EngineConfig, GatingConfig,
                              MeshConfig, RecyclingConfig)

G, W, D, SQ, T = 4, 16, 5, 3, 10
STRIDE = 1 << 16
out = {"devices": len(jax.devices())}


def tiles(seed, g, words_n, t=T, density=0.7):
    rng = np.random.default_rng(seed)
    bits = rng.random((t, g, W, words_n)) < density
    return jax.vmap(jax.vmap(jaxsim.pack_tile))(jnp.asarray(bits))


def saturated(g, words_n, t=T):
    return jnp.asarray(np.full((t, g, W, words_n), 0xFFFFFFFF, np.uint32))


FAMS = {
    "plain": {},
    "gated": dict(gating=GatingConfig()),
    "recycled": dict(recycling=RecyclingConfig(watermark=8,
                                               id_stride=STRIDE)),
    "gated_recycled": dict(recycling=RecyclingConfig(watermark=8,
                                                     id_stride=STRIDE),
                           gating=GatingConfig()),
}
for fam, kw in FAMS.items():
    cfg = EngineConfig(groups=G, window=W, n_diss=D, n_seq=SQ,
                       order_budget=4, merge_capacity=4096,
                       mesh=MeshConfig(), **kw)
    # recycled families: saturated position-uniform traffic so the run
    # retires prefixes and mints fresh per-group ids mid-run; the fresh
    # ids land in later merge rounds, so a wrong shard-local id base
    # shows up directly in the merged prefix below
    if cfg.recycling is not None:
        acks, votes = saturated(G, (D + 31) // 32), saturated(
            G, (SQ + 31) // 32)
    else:
        seed = {"plain": 11, "gated": 13}[fam]  # str hash is salted
        acks = tiles(seed, G, D)
        votes = tiles(seed + 1, G, SQ, density=0.6)
    holds = saturated(G, (cfg.gating.n_diss_partition + 31) // 32) \
        if cfg.gating else None
    st, merged, cnt, com = api.run(cfg, api.create_state(cfg), acks,
                                   votes, holds)
    rec = {"merged": np.asarray(merged[:int(cnt)]).tolist(),
           "count": int(cnt), "committed": int(com)}
    if cfg.recycling is not None:
        rs = st.core.rs if cfg.family == "gated_recycled" else st.core
        rec["retired"] = np.asarray(rs.retired).tolist()
    out[fam] = rec

# padded mesh: 6 groups on a 4-device slice (pad = 2 inert rows)
cfgp = EngineConfig(groups=6, window=W, n_diss=D, n_seq=SQ,
                    order_budget=4, merge_capacity=4096,
                    mesh=MeshConfig(n_devices=4))
acks, votes = tiles(7, 6, D), tiles(8, 6, SQ, density=0.6)
_, merged, cnt, com = api.run(cfgp, api.create_state(cfgp), acks, votes)
out["padded"] = {"merged": np.asarray(merged[:int(cnt)]).tolist(),
                 "count": int(cnt), "committed": int(com)}

# epoch reconfiguration on sharded state: active rows (0, 1) -> (0, 1, 2)
table = EP.EpochTable(((0, 1), (0, 1, 2)), n_rows=3)
cfge = EngineConfig(groups=3, window=W, n_diss=D, n_seq=SQ,
                    order_budget=4, merge_capacity=4096,
                    recycling=RecyclingConfig(watermark=8,
                                              id_stride=STRIDE),
                    epochs=table, mesh=MeshConfig())
wd, ws = (D + 31) // 32, (SQ + 31) // 32
acks0 = np.zeros((T, 3, W, wd), np.uint32)
acks0[:, (0, 1)] = 0xFFFFFFFF
eng = Engine.create(cfge)
eng.run(jnp.asarray(acks0), saturated(3, ws))
za = jnp.zeros((3, W, wd), jnp.uint32)
zv = jnp.full((3, W, ws), jnp.uint32(0xFFFFFFFF))
drain = 0
while not EP.is_drained(eng.state.core.q) and drain < 32:
    eng.tick(za, zv)
    drain += 1
assert EP.is_drained(eng.state.core.q)
report = eng.reconfigure(1)
eng.run(saturated(3, wd), saturated(3, ws))
merged, cnt, com = eng.committed()
out["reconfig"] = {"merged": np.asarray(merged[:int(cnt)]).tolist(),
                   "count": int(cnt), "committed": int(com),
                   "moved": int(report["moved"]),
                   "drain_ticks": drain}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def device_runs():
    src = Path(__file__).resolve().parent.parent / "src"
    runs = {}
    for ndev in (1, 8):
        # the children emulate devices on the host CPU and must never
        # reach for an accelerator the parent may hold
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
            PYTHONPATH=str(src) + os.pathsep + os.environ.get(
                "PYTHONPATH", ""))
        proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                              capture_output=True, text=True,
                              timeout=900)
        assert proc.returncode == 0, proc.stderr[-4000:]
        runs[ndev] = json.loads(proc.stdout.splitlines()[-1])
    return runs


def test_one_vs_eight_devices_bit_identical(device_runs):
    one, eight = device_runs[1], device_runs[8]
    assert one["devices"] == 1 and eight["devices"] == 8
    for key in one:
        if key != "devices":
            assert one[key] == eight[key], key


def test_cross_device_scenarios_are_substantive(device_runs):
    """The bit-identity above would pass vacuously on empty logs — pin
    that every scenario ordered ids, the recycled runs actually retired
    (fresh ids were minted mid-run), and the reconfig moved rows."""
    r = device_runs[1]
    for fam in ("plain", "gated", "recycled", "gated_recycled",
                "padded", "reconfig"):
        assert r[fam]["count"] > 0, fam
        assert r[fam]["committed"] > 0, fam
    assert sum(r["recycled"]["retired"]) > 0
    assert sum(r["gated_recycled"]["retired"]) > 0
    assert r["reconfig"]["moved"] > 0
