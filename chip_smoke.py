"""Bring-up smoke run of the HT-Paxos replicated log on a TPU.

One chip (no arguments):

1. **paper-scale pipeline** — the paper's clustered data center (§5):
   m=1000 disseminators split into G=4 partitions of 250 (§5.5), s=20
   sequencers, 10⁵ clients sending 1 KiB and 512 B requests, a window of
   2048 ids per group with stability gating and window recycling. Traffic
   is drawn from ``--seed`` by ``WorkloadModel`` and driven through the
   user entry points ``init_pipeline`` → ``build_route_table`` →
   ``run_pipeline`` → ``committed`` → ``decode_merged``. Checked: no
   overflow, ids committed, admission records equal to the host twin
   ``plan_admissions``, per-lane wire bytes equal to the batches flushed;
2. **DES reference** — a small configuration's committed batch order
   equals the ``HTPaxosSim`` learners' order on the same pre-drawn
   workload (``repro.pipeline.crosscheck``);
3. **kernels** — ``stability_update_grouped``, ``quorum_update_grouped``
   and ``quorum_update`` compiled for the chip at the deployment shape,
   equal to their jnp references.

Four chips (``--chips 4``): only the meshed engine — ``api.run`` on the
gated, recycled family at G=8 sharded over a 4-device group mesh — against
the same configuration without a mesh on one device: the merged committed
prefix must hash equal, and the run must have recycled.

Earlier lines are readings for a person; times are smoke readings, not
metrics. The last line is one JSON object, ``{"ok": true, "device":
{...}}``. Any failed check, a platform other than TPU, or a directory
without this repository's ``src/`` exits non-zero with no result line.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# the paper's clustered data center (§5.1–5.2, §5.5)
N_DISS, GROUPS, N_SEQ, N_CLIENTS, WINDOW = 1000, 4, 20, 100_000, 2048
SIZES, SIZE_PROBS = (1024, 512), (0.5, 0.5)
ARRIVAL_RATE = 0.1        # per client-tick: ~10 requests per lane-tick
BUDGET_BYTES = 8192       # batch byte budget: ~half the lane-ticks close one
ORDER_BUDGET = 512        # ids a group's leader orders per tick
CAPACITY = 1 << 14        # admission ranks per group (= id stride)
TICKS = 32                # ticks of drawn traffic in the pipeline phase
MESH_DEVICES, MESH_GROUPS, MESH_TICKS = 4, 8, 24


def fail(msg: str):
    raise SystemExit(f"chip_smoke: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def random_bitsets(key, lead: tuple, n: int, p: float):
    """Packed node bitsets ``uint32[*lead, ⌈n/32⌉]``: each of the ``n``
    node bits set with probability ``p``, the bits past ``n`` clear."""
    import jax
    from repro.core.jaxsim import pack_tile
    flags = jax.random.bernoulli(key, p, lead + (n,))
    return pack_tile(flags.reshape(-1, n)).reshape(lead + (-1,))


def paper_config(seed: int):
    """The deployment as a ``PipelineConfig`` sized for ``TICKS`` ticks:
    per-node delivery lags of 1–3 ticks drawn from ``seed``."""
    from repro.engine.api import EngineConfig, GatingConfig, RecyclingConfig
    from repro.pipeline import PipelineConfig
    rng = np.random.default_rng(seed)
    part = N_DISS // GROUPS
    return PipelineConfig(
        engine=EngineConfig(
            groups=GROUPS, window=WINDOW, n_diss=N_DISS, n_seq=N_SEQ,
            order_budget=ORDER_BUDGET,
            merge_capacity=TICKS * ORDER_BUDGET,
            recycling=RecyclingConfig(watermark=WINDOW // 4,
                                      id_stride=CAPACITY),
            gating=GatingConfig(n_diss_partition=part)),
        n_clients=N_CLIENTS, budget_bytes=BUDGET_BYTES,
        ack_lag=tuple(rng.integers(1, 4, N_DISS)),
        hold_lag=tuple(rng.integers(1, 4, part)),
        vote_lag=tuple(rng.integers(1, 4, N_SEQ)),
        capacity=CAPACITY, seq_capacity=4 * TICKS)


def pipeline_phase(seed: int) -> dict:
    """Run the deployment over ``TICKS`` ticks of drawn traffic and check
    it against the host twin. Returns the readings to print."""
    import jax
    import jax.numpy as jnp
    from repro.dissem.batcher import EMPTY_BATCH_BYTES, request_wire_bytes
    from repro.pipeline import (WorkloadModel, build_route_table, committed,
                                decode_merged, init_pipeline,
                                plan_admissions, run_pipeline)

    pcfg = paper_config(seed)
    model = WorkloadModel(n_clients=pcfg.n_clients,
                          arrival_rate=ARRIVAL_RATE, size_choices=SIZES,
                          size_probs=SIZE_PROBS)
    wl = model.draw(jax.random.PRNGKey(seed), TICKS)
    rt_host = build_route_table(pcfg)
    rt = jnp.asarray(rt_host)

    t0 = time.perf_counter()
    compiled = run_pipeline.lower(pcfg, init_pipeline(pcfg), wl.arrived,
                                  wl.sizes, rt).compile()
    compile_s = time.perf_counter() - t0
    st, outs = compiled(init_pipeline(pcfg), wl.arrived, wl.sizes, rt)
    jax.block_until_ready(st)

    st2 = init_pipeline(pcfg)
    jax.block_until_ready(st2)
    t0 = time.perf_counter()
    st2, _ = compiled(st2, wl.arrived, wl.sizes, rt)
    jax.block_until_ready(st2)
    wall_s = time.perf_counter() - t0

    check(not bool(st.overflowed), "pipeline admission record overflowed")
    check(int(st.engine.merge.overflowed.sum()) == 0,
          "merge log overflowed")
    check(int(outs["dropped"].sum()) == 0, "merge entries were dropped")
    merged, count, com = committed(pcfg, st)
    com = int(com)
    check(com > 0, "no ids committed")
    bids = decode_merged(pcfg, st, merged, com)
    check(len(set(bids)) == len(bids), "a batch was committed twice")

    # admission records: the jit path against the host twin
    admits = plan_admissions(pcfg, wl, rt_host)
    codes = np.asarray(st.bid_code)
    adm_ticks = np.asarray(st.admit_tick)
    counts = np.asarray(st.admit_count)
    for g, rows in admits.items():
        n = len(rows)
        check(counts[g] == n, f"group {g}: {counts[g]} admitted, twin {n}")
        want_code = np.array([r["lane"] * pcfg.seq_capacity + r["seq"]
                              for r in rows], np.int32)
        want_tick = np.array([r["tick"] for r in rows], np.int32)
        check(np.array_equal(codes[g, :n], want_code)
              and (codes[g, n:] == -1).all(),
              f"group {g}: bid codes differ from the host twin")
        check(np.array_equal(adm_ticks[g, :n], want_tick),
              f"group {g}: admission ticks differ from the host twin")

    # per-lane wire bytes: every request's wire cost plus one header per
    # batch the twin flushed on that lane
    D = pcfg.n_lanes
    batches = np.zeros(D, np.int64)
    for rows in admits.values():
        for r in rows:
            batches[r["lane"]] += 1
    arrived, sizes = np.asarray(wl.arrived), np.asarray(wl.sizes)
    req = np.where(arrived, request_wire_bytes(0) + sizes.astype(np.int64),
                   0).sum(axis=0)
    want_bytes = np.bincount(np.arange(pcfg.n_clients) % D, weights=req,
                             minlength=D) + batches * EMPTY_BATCH_BYTES
    check(np.array_equal(np.asarray(st.n_flushed), batches),
          "per-lane batch counts differ from the host twin")
    check(np.array_equal(np.asarray(st.flushed_bytes),
                         want_bytes.astype(np.int64)),
          "per-lane wire bytes differ from the batches flushed")
    return {"ticks": TICKS, "requests": wl.n_requests,
            "batches": int(batches.sum()), "committed_ids": com,
            "committed_batches": len(bids), "compile_s": compile_s,
            "wall_s": wall_s}


def des_phase() -> dict:
    """Small configuration: committed order equals the DES learners'."""
    from repro.pipeline.crosscheck import pipeline_vs_des
    G, D = 4, 12
    r = pipeline_vs_des(G, D)
    check(not r["overflowed"] and r["dropped"] == 0,
          "DES-check pipeline overflowed or dropped entries")
    check(r["committed"] == r["admitted"] == len(r["plan"]),
          "DES-check pipeline did not commit every batch")
    check(r["jax_order"] == r["des_order"],
          "committed order differs from the DES learners' order")
    return {"groups": G, "n_diss": D, "batches": len(r["des_order"])}


def kernel_phase(seed: int) -> list[str]:
    """The Pallas kernels at the deployment shape against their jnp
    references, two passes each so the second starts from carried
    state."""
    import jax
    import jax.numpy as jnp
    from repro.dissem.engine import DissemState, stability_tick
    from repro.kernels import ref
    from repro.kernels.dissem import stability_update_grouped
    from repro.kernels.quorum import quorum_update, quorum_update_grouped

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)

    def tiles(key, n):
        return random_bitsets(key, (2, GROUPS, WINDOW), n, 0.5)

    def same(a, b):
        return bool(jnp.array_equal(a, b))

    done = []
    part = N_DISS // GROUPS
    maj = part // 2 + 1
    holds = tiles(keys[0], part)
    st_k = st_r = DissemState(jnp.zeros_like(holds[0]),
                              jnp.zeros((GROUPS, WINDOW), jnp.bool_))
    for t in range(2):
        bits, counts, stable, newly = stability_update_grouped(
            st_k.hold_bits, holds[t], st_k.stable, majority=maj,
            interpret=False)
        st_r, out = stability_tick(st_r, holds[t], majority=maj)
        check(same(bits, st_r.hold_bits) and same(stable, st_r.stable)
              and same(counts, out["counts"])
              and same(newly, out["newly_stable"].sum(axis=1)),
              f"stability_update_grouped differs from stability_tick "
              f"(pass {t})")
        st_k = DissemState(bits, stable)
    check(int(st_r.stable.sum()) > 0, "stability kernel check is vacuous")
    done.append(f"stability_update_grouped[{GROUPS},{WINDOW},"
                f"{holds.shape[-1]}]")

    maj = N_DISS // 2 + 1
    acks = tiles(keys[1], N_DISS)
    want_ref = jax.vmap(lambda b, u, s: ref.quorum_ref(b, u, s,
                                                       majority=maj))
    bits_k = bits_r = jnp.zeros_like(acks[0])
    stab_k = stab_r = jax.random.bernoulli(keys[2], 0.1, (GROUPS, WINDOW))
    for t in range(2):
        got = quorum_update_grouped(bits_k, acks[t], stab_k, majority=maj,
                                    interpret=False)
        want = want_ref(bits_r, acks[t], stab_r)
        check(all(same(g, w) for g, w in zip(got, want)),
              f"quorum_update_grouped differs from quorum_ref (pass {t})")
        bits_k, _, stab_k = got
        bits_r, _, stab_r = want
        one = quorum_update(bits_k[0], acks[t, 0], stab_k[0], majority=maj,
                            interpret=False)
        check(all(same(g, w) for g, w in zip(
            one, ref.quorum_ref(bits_k[0], acks[t, 0], stab_k[0],
                                majority=maj))),
              f"quorum_update differs from quorum_ref (pass {t})")
    done.append(f"quorum_update_grouped[{GROUPS},{WINDOW},"
                f"{acks.shape[-1]}]")
    done.append(f"quorum_update[{WINDOW},{acks.shape[-1]}]")
    return done


def mesh_phase(seed: int) -> dict:
    """``api.run`` sharded over a ``MESH_DEVICES`` group mesh against the
    same configuration without a mesh on one device."""
    import jax
    import jax.numpy as jnp
    from repro.engine import api
    from repro.engine.api import (EngineConfig, GatingConfig, MeshConfig,
                                  RecyclingConfig)
    from repro.launch.mesh import make_group_mesh

    groups, ticks, n_devices = MESH_GROUPS, MESH_TICKS, MESH_DEVICES
    part = N_DISS // groups
    kw = dict(groups=groups, window=WINDOW, n_diss=N_DISS, n_seq=N_SEQ,
              order_budget=ORDER_BUDGET,
              merge_capacity=ticks * ORDER_BUDGET,
              recycling=RecyclingConfig(watermark=WINDOW // 4,
                                        id_stride=CAPACITY),
              gating=GatingConfig(n_diss_partition=part))
    base = EngineConfig(**kw)
    meshed = EngineConfig(**kw, mesh=MeshConfig(n_devices=n_devices))
    mesh = make_group_mesh(groups, n_devices=n_devices)
    check(mesh.devices.size == n_devices,
          f"group mesh spans {mesh.devices.size} devices, not {n_devices}")

    # position-uniform traffic (the recycled families' contract): one
    # random node mask per (tick, group), the same for every slot
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)

    def traffic(key, n):
        bits = random_bitsets(key, (ticks, groups, 1), n, 0.25)
        return jnp.broadcast_to(bits, (ticks, groups, WINDOW,
                                       bits.shape[-1]))

    acks, votes, holds = (traffic(keys[0], N_DISS), traffic(keys[1], N_SEQ),
                          traffic(keys[2], part))

    def run(cfg):
        t0 = time.perf_counter()
        st, merged, count, com = api.run(cfg, api.create_state(cfg), acks,
                                         votes, holds)
        jax.block_until_ready(merged)
        first_s = time.perf_counter() - t0
        com = int(com)
        digest = hashlib.sha256(
            np.asarray(merged[:com]).tobytes()).hexdigest()
        retired = int(st.core.rs.retired.sum())
        devices = {d for leaf in jax.tree.leaves(st.core)
                   for d in leaf.sharding.device_set}
        return {"committed": com, "count": int(count), "sha256": digest,
                "retired": retired, "devices": len(devices),
                "first_call_s": first_s}

    one, sharded = run(base), run(meshed)
    check(sharded["devices"] == n_devices,
          f"meshed state lives on {sharded['devices']} devices")
    check(one["committed"] > 0, "mesh phase committed nothing")
    check(one["retired"] > 0 and sharded["retired"] > 0,
          "mesh phase never recycled")
    check(one["sha256"] == sharded["sha256"]
          and one["committed"] == sharded["committed"]
          and one["count"] == sharded["count"],
          f"merged prefix differs: one device {one}, mesh {sharded}")
    return {"one_device": one, "mesh": sharded,
            "mesh_devices": int(mesh.devices.size)}


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: paper-scale pipeline, DES check and kernels; "
                        "4: only the meshed engine against one device")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"no repository source at {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()
    platform = dev[0].platform
    if platform != "tpu":
        fail(f"needs a TPU, found platform {platform!r}")
    if len(dev) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{len(dev)}")
    cache = use_compile_cache(SRC.parent)
    print(f"device: {dev[0].device_kind} x{len(dev)} ({platform}); "
          f"compile cache {cache}")

    if args.chips == 4:
        r = mesh_phase(args.seed)
        print(f"mesh: {r['mesh_devices']}-device group mesh, "
              f"G={MESH_GROUPS}, "
              f"committed {r['mesh']['committed']} ids, retired "
              f"{r['mesh']['retired']}; sha256 {r['mesh']['sha256']} "
              f"== one device {r['one_device']['sha256']}")
        print(f"smoke reading, not a metric: first call (compile + run) "
              f"{r['mesh']['first_call_s']} s meshed, "
              f"{r['one_device']['first_call_s']} s one device")
    else:
        r = pipeline_phase(args.seed)
        print(f"pipeline: m={N_DISS} G={GROUPS} s={N_SEQ} "
              f"clients={N_CLIENTS} window={WINDOW}: {r['requests']} "
              f"requests in {r['batches']} batches, {r['ticks']} ticks, "
              f"committed ids {r['committed_ids']}; admissions == "
              f"plan_admissions, per-lane wire bytes == batches flushed")
        print(f"pipeline compile s: {r['compile_s']}")
        print(f"smoke reading, not a metric: steady run of {r['ticks']} "
              f"ticks {r['wall_s']} s")
        d = des_phase()
        print(f"des: G={d['groups']} D={d['n_diss']}: {d['batches']} "
              f"batches, committed order == HTPaxosSim learners' order")
        for k in kernel_phase(args.seed):
            print(f"kernel compiled (interpret=False) == reference: {k}")

    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))


if __name__ == "__main__":
    main()
