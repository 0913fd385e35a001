"""Closed-pipeline cross-validation against the DES on shared traffic.

The SAME pre-drawn workload arrays drive both the closed in-jax pipeline
(:mod:`repro.pipeline.closed`) and the discrete-event simulator
(``HTPaxosSim`` via ``HTConfig.workload_schedule``), and both must
produce the identical learner batch order. Neither side is derived from
the other's trace, so this validates the whole chain: client→lane
assignment, byte-budget batching, bid sequencing, epoch routing,
stability gating, ordering, and the round-robin merge. The DES is the
reference.

Alignment construction (what makes bit-equality *provable* rather than
coincidental): time is cut into cycles of the DES skip period P; each
cycle either injects exactly one batch per active ordering group
(covering lanes found greedily against the shared crc32 router) or
nothing at all. Batches are injected 4 ticks before the next skip-timer
fire, so every active group's leader has the proposal in flight at the
fire and never no-ops; idle/inactive rows no-op exactly once per cycle.
Every row therefore advances exactly one rank per non-quiet cycle on
the DES side, while the engine's SKIP padding (``entries_from_assigned``
pads all rows to the per-tick max) enforces the same rank alignment on
the jax side — so after dropping control entries, both round-robin
merges interleave the real batches identically: cycle by cycle,
ascending group index. A mid-run membership switch stays aligned
because both sides charge the epoch marker one rank in every row.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.classic import OrderingConfig
from ..core.htpaxos import HTConfig, HTPaxosSim
from ..engine.api import EngineConfig, GatingConfig, RecyclingConfig
from ..engine.epochs import EpochTable, route_id_epoch
from .closed import (PipelineConfig, build_route_table, committed,
                     decode_merged, init_pipeline, pipeline_tick_jit,
                     run_pipeline)
from .workload import Workload

P = 8           # DES skip period = one alignment cycle
BUDGET = 4096   # byte budget: roomy, so one flush = one batch


def greedy_cover_schedule(n_lanes, actives, epochs, table):
    """Per cycle, pick one lane per active group whose *next* bid routes
    there (each lane used at most once per cycle). Returns
    [(cycle, lane, seq, group), ...]; raises if no cover exists — the
    construction is deterministic, so a config that builds once builds
    forever."""
    seqs = [0] * n_lanes
    plan = []
    for cyc, (active, ep) in enumerate(zip(actives, epochs)):
        owners = {d: route_id_epoch((f"d{d}", seqs[d]), table, ep)
                  for d in range(n_lanes)}
        used = set()
        for g in active:
            cand = [d for d in range(n_lanes)
                    if owners[d] == g and d not in used]
            if not cand:
                raise AssertionError(
                    f"cover construction stuck at cycle {cyc} for group "
                    f"{g}: next bids route to {owners}")
            d = cand[0]
            used.add(d)
            plan.append((cyc, d, seqs[d], g))
            seqs[d] += 1
    return plan


def cover_workload(plan, n_cycles, n_lanes, n_clients):
    """Workload arrays from a cover plan: batch (cycle, lane) becomes a
    request from client=lane at tick=cycle; every third cycle one lane
    also gets a second request from client lane+n_lanes (same lane, so
    the two requests share one batch — exercising multi-request
    batches without disturbing the one-batch-per-group cover)."""
    events = []
    for i, (cyc, lane, _seq, _g) in enumerate(plan):
        size = 200 + 37 * ((7 * cyc + 13 * lane) % 20)
        events.append((cyc, lane, size))
        if i % 3 == 0 and n_clients >= n_lanes + lane + 1:
            events.append((cyc, n_lanes + lane,
                           150 + 29 * (cyc % 11)))
    return Workload.from_schedule(events, ticks=n_cycles,
                                  n_clients=n_clients)


def cover_pipeline_config(G, D, *, table=None, capacity=256):
    """The pipeline side of the construction: window 8, 3 sequencers,
    the global disseminator set as every group's partition, and the
    same byte budget the DES batches with."""
    return PipelineConfig(
        engine=EngineConfig(
            groups=G, window=8, n_diss=D, n_seq=3, order_budget=4,
            merge_capacity=G * 512,
            recycling=RecyclingConfig(watermark=4, id_stride=4096),
            gating=GatingConfig(stab_majority=D // 2 + 1,
                                n_diss_partition=D),
            epochs=table),
        n_clients=2 * D, budget_bytes=BUDGET,
        capacity=capacity, seq_capacity=64)


def drain(pcfg, st, rt, max_ticks=24):
    """Tick with no arrivals until every admitted batch is committed."""
    empty_a = jnp.zeros((pcfg.n_clients,), bool)
    empty_s = jnp.zeros((pcfg.n_clients,), jnp.int32)
    for _ in range(max_ticks):
        st, _ = pipeline_tick_jit(pcfg, st, empty_a, empty_s, rt)
        _, _, com = committed(pcfg, st)
        if int(com) == int(st.admit_count.sum()):
            break
    return st


def des_schedule(workload):
    """Map workload ticks to DES times: tick k → kP + (P-4), so the
    proposal is in flight at the next skip fire (see module docstring)."""
    return tuple((cyc * P + (P - 4.0), client, size)
                 for (cyc, client, size) in workload.schedule())


def run_des(G, D, workload, *, reconfig=None, until):
    """The DES reference on the same workload: returns (sim, the learner
    batch order), after checking the merged-interleaving invariant and
    that all learners agree."""
    cfg = HTConfig(
        n_diss=D, n_seq=3, n_clients=2 * D,
        batch_budget_bytes=BUDGET, random_client_target=False,
        n_groups=G, group_skip_interval=float(P),
        ordering=OrderingConfig(order_batch_max=1),
        reconfig_schedule=reconfig or (),
        workload_schedule=des_schedule(workload))
    sim = HTPaxosSim(cfg, requests_per_client=0)
    sim.run(until=until)
    if sim.check_merged_interleaving() != []:
        raise AssertionError("DES merged interleaving invariant violated")
    orders = [list(a.executed_bid_order) for a in sim.all_learner_agents()]
    if any(o != orders[0] for o in orders):
        raise AssertionError("DES learners diverged among themselves")
    return sim, orders[0]


def pipeline_vs_des(G, D, n_cycles=12):
    """Run the static-membership construction on both sides. Returns a
    dict: ``plan`` (the cover), ``jax_order`` / ``des_order`` (learner
    batch orders), and the pipeline's ``admitted`` / ``committed``
    counts, ``dropped`` merge entries and ``overflowed`` flag."""
    table = EpochTable((tuple(range(G)),), n_rows=G)
    plan = greedy_cover_schedule(
        D, [tuple(range(G))] * n_cycles, [0] * n_cycles, table)
    wl = cover_workload(plan, n_cycles, D, 2 * D)

    pcfg = cover_pipeline_config(G, D)
    rt = jnp.asarray(build_route_table(pcfg))
    st = init_pipeline(pcfg)
    st, outs = run_pipeline(pcfg, st, wl.arrived, wl.sizes, rt)
    st = drain(pcfg, st, rt)
    merged, _, com = committed(pcfg, st)
    _, des_order = run_des(G, D, wl, until=n_cycles * P + 20)
    return {"plan": plan,
            "jax_order": decode_merged(pcfg, st, merged, com),
            "des_order": des_order,
            "admitted": int(st.admit_count.sum()),
            "committed": int(com),
            "dropped": int(outs["dropped"].sum()),
            "overflowed": bool(st.overflowed)}
