"""The closed in-jax pipeline: workload → batcher → stability → ordering.

One jit-compiled :func:`pipeline_tick` spans all four decoupled HT-Paxos
stages (§4.1), entirely on-device:

1. **workload** — the tick's client arrivals (pre-drawn
   :class:`~repro.pipeline.workload.Workload` arrays) are gathered to
   their statically-assigned disseminator lanes (client ``c`` → lane
   ``c mod n_diss``);
2. **batcher** — each lane runs the byte-budget accumulator
   (:mod:`repro.pipeline.vbatch`, §4.1 step 13) and flushes batches,
   each stamped ``(lane d, seq)`` — exactly the DES twin's
   ``(node_id, next_batch)`` identity;
3. **delivery / stability** — flushed batches are *admitted* to their
   owner ordering group (epoch-aware route table, crc32 of the bid —
   the same hash the DES routes with) and a per-node lag schedule
   models replication: a batch admitted at tick ``t`` is held (hold
   bit), replicated (ack bit) and vote-acknowledged (vote bit) by node
   ``j`` once its age reaches ``hold_lag[j]`` / ``ack_lag[j]`` /
   ``vote_lag[j]``. Tiles are *recomputed from age every tick* against
   the engine's **live** slot→id map, so the model stays exact across
   window recycling (absorption is idempotent OR);
4. **ordering** — one facade :func:`repro.engine.api.tick` (the gated,
   epoch-aware engine) absorbs the tiles and appends to the merged
   consumable log.

The pipeline addresses engine slots by **global rank**: group ``g``'s
``k``-th admitted batch is matched to engine id ``g·stride + k``
(``stride`` = ``id_stride`` for recycled families, ``window``
otherwise) — the exact id sequence the engine assigns in admission
order, so no per-slot bookkeeping has to chase the recycler's
compaction. ``admit_tick[g, k]`` / ``bid_code[g, k]`` record each
rank's admission time and batch identity; :func:`decode_merged` maps
the merged log back to ``(lane, seq)`` bids for the cross-validation
against ``HTPaxosSim`` learners.

Each stage runs inside a ``jax.named_scope`` named in
``repro.pipeline.STAGES`` (``ht.gather`` ... ``ht.commit_gate``, defined
in :mod:`repro.engine.stages`),
so the compiled program and a device trace attribute every op to its
stage; the engine stages carry their scopes in the engine's own
functions. Every tick also returns boundary counters (see
:func:`pipeline_tick`), which :func:`run_pipeline` stacks per tick.

Reconfiguration is drain-then-switch at *quiescent* boundaries:
:func:`reconfigure_pipeline` refuses to re-home in-flight ids (the
rank addressing is per-row; a moved id would be unreachable by the
delivery model) — drain first, exactly like the DES's admin event.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..dissem.batcher import BatchAccumulator, EMPTY_BATCH_BYTES
from ..engine import adaptive as adaptive_mod
from ..engine import api
from ..engine.api import EngineConfig, EngineState
from ..engine.epochs import EpochTable, route_id_epoch
from ..engine.stages import ADMISSION, GATHER, LAG_TILES
from .vbatch import BatchState, init_batch_state, tick_flushes
from .workload import Workload


def lane_bid(lane: int, seq: int) -> tuple[str, int]:
    """The DES-identical batch id of lane ``lane``'s ``seq``-th batch:
    ``("d<lane>", seq)`` — same tuple, same repr, same crc32 route."""
    return (f"d{lane}", seq)


@dataclass(frozen=True)
class PipelineConfig:
    """Static shape/model of one closed pipeline (hashable → jit-static).

    ``engine`` must be a gated family (the pipeline exists to drive the
    stability gate). ``ack_lag`` / ``hold_lag`` / ``vote_lag`` are the
    per-node delivery lags in ticks (lengths ``n_diss`` /
    ``gating.n_diss_partition`` / ``n_seq``). ``capacity`` bounds the
    per-group admission record (ranks outstanding across the whole run
    segment); ``seq_capacity`` bounds per-lane batch sequence numbers
    (the route table's width)."""
    engine: EngineConfig
    n_clients: int
    budget_bytes: int
    max_requests: int | None = None
    ack_lag: tuple[int, ...] = ()
    hold_lag: tuple[int, ...] = ()
    vote_lag: tuple[int, ...] = ()
    capacity: int = 1024
    seq_capacity: int = 1024

    def __post_init__(self):
        e = self.engine
        if e.gating is None:
            raise ValueError(
                "PipelineConfig.engine must be a gated family (gating="
                "GatingConfig(...)): the closed pipeline's delivery model "
                "drives the dissemination-stability gate")
        if self.n_clients < 1:
            raise ValueError(f"n_clients must be >= 1, got {self.n_clients}")
        if self.budget_bytes <= EMPTY_BATCH_BYTES:
            raise ValueError(
                f"budget_bytes={self.budget_bytes} cannot fit the batch "
                f"header ({EMPTY_BATCH_BYTES} B) plus any request")
        if self.max_requests is not None and self.max_requests < 1:
            raise ValueError(
                f"max_requests must be >= 1 or None, got {self.max_requests}")
        def norm_lags(name, lags, n, role):
            lags = tuple(int(x) for x in lags) if lags else (0,) * n
            if len(lags) != n:
                raise ValueError(
                    f"PipelineConfig.{name} has {len(lags)} entries, needs "
                    f"one per {role} ({n})")
            if any(x < 0 for x in lags):
                raise ValueError(f"PipelineConfig.{name} has negative lags: "
                                 f"{lags}")
            object.__setattr__(self, name, lags)
        norm_lags("ack_lag", self.ack_lag, e.n_diss, "disseminator")
        norm_lags("hold_lag", self.hold_lag, e.gating.n_diss_partition,
                  "gating-partition node")
        norm_lags("vote_lag", self.vote_lag, e.n_seq, "sequencer")
        if self.capacity < e.window:
            raise ValueError(
                f"capacity={self.capacity} < window={e.window}: the engine "
                "can hold more live ranks than the admission record")
        if self.capacity > self.id_stride:
            raise ValueError(
                f"capacity={self.capacity} > id stride={self.id_stride}: "
                "rank g*stride+k would alias into the next group's id range "
                "before the admission record fills")
        if self.seq_capacity < 1:
            raise ValueError(
                f"seq_capacity must be >= 1, got {self.seq_capacity}")

    @property
    def id_stride(self) -> int:
        """Engine-id stride between group rows (rank k ↔ id g·stride+k)."""
        e = self.engine
        return e.recycling.id_stride if e.recycling is not None else e.window

    @property
    def n_lanes(self) -> int:
        return self.engine.n_diss

    @property
    def lane_slots(self) -> int:
        """Request slots per lane per tick (clients are dealt round-robin
        over lanes)."""
        return -(-self.n_clients // self.n_lanes)

    def lane_clients(self) -> tuple[np.ndarray, np.ndarray]:
        """Static client index/mask per lane: int[D, K], bool[D, K] —
        lane d serves clients d, d+D, d+2D, ... (the DES's fixed
        client→disseminator rule)."""
        D, K = self.n_lanes, self.lane_slots
        idx = np.zeros((D, K), np.int32)
        mask = np.zeros((D, K), bool)
        for d in range(D):
            cs = np.arange(d, self.n_clients, D)
            idx[d, :len(cs)] = cs
            mask[d, :len(cs)] = True
        return idx, mask


class PipelineState(NamedTuple):
    """The closed pipeline's carried pytree."""
    engine: EngineState
    batch: BatchState
    admit_count: jax.Array      # int32[G] ranks admitted per group
    admit_tick: jax.Array       # int32[G, R] admission tick per rank
    bid_code: jax.Array         # int32[G, R] lane*seq_capacity+seq, -1 empty
    flushed_bytes: jax.Array    # int32[D] cumulative wire bytes per lane
    n_flushed: jax.Array        # int32[D] cumulative batches per lane
    tick: jax.Array             # int32 scalar
    overflowed: jax.Array       # bool scalar: capacity/seq_capacity blown


def init_pipeline(cfg: PipelineConfig) -> PipelineState:
    G, R, D = cfg.engine.groups, cfg.capacity, cfg.n_lanes
    return PipelineState(
        engine=api.create_state(cfg.engine),
        batch=init_batch_state(D),
        admit_count=jnp.zeros((G,), jnp.int32),
        admit_tick=jnp.zeros((G, R), jnp.int32),
        bid_code=jnp.full((G, R), -1, jnp.int32),
        flushed_bytes=jnp.zeros((D,), jnp.int32),
        n_flushed=jnp.zeros((D,), jnp.int32),
        tick=jnp.int32(0),
        overflowed=jnp.bool_(False))


def build_route_table(cfg: PipelineConfig, epoch: int = 0,
                      table: EpochTable | None = None) -> np.ndarray:
    """Owner group of every possible bid ``(lane, seq)`` at ``epoch``:
    int32[D, seq_capacity], computed with the *DES's own* hash
    (``route_id_epoch`` → crc32 of the bid tuple's repr) so both sides
    of the cross-validation route identically. ``table`` defaults to
    ``engine.epochs`` or, absent that, the static all-rows table."""
    if table is None:
        table = cfg.engine.epochs
    if table is None:
        table = EpochTable((tuple(range(cfg.engine.groups)),),
                           n_rows=cfg.engine.groups)
    out = np.empty((cfg.n_lanes, cfg.seq_capacity), np.int32)
    for d in range(cfg.n_lanes):
        for s in range(cfg.seq_capacity):
            out[d, s] = route_id_epoch(lane_bid(d, s), table, epoch)
    return out


def _lag_masks(lags: tuple[int, ...]) -> list[tuple[int, np.ndarray]]:
    """Static pack of a lag schedule: ``[(lag, node_mask), ...]`` with
    one packed uint32[words] mask per *distinct* lag value (low bit =
    node 0). The per-tick tile build then costs one compare + select
    per distinct lag instead of one per node — with the common uniform
    schedule that is a single select per slot."""
    words = (len(lags) + 31) // 32
    out = []
    for lag in sorted(set(lags)):
        mask = np.zeros((words,), np.uint32)
        for j, x in enumerate(lags):
            if x == lag:
                mask[j // 32] |= np.uint32(1 << (j % 32))
        out.append((lag, mask))
    return out


def _lag_tiles(cfg: PipelineConfig, state: PipelineState)\
        -> tuple[jax.Array, jax.Array, jax.Array]:
    """Recompute (acks, votes, holds) packed tiles from admission ages
    against the engine's live slot→id map."""
    G = cfg.engine.groups
    sids = api.slot_ids(state.engine)                       # int32[G, W]
    base = (jnp.arange(G, dtype=sids.dtype) * cfg.id_stride)[:, None]
    rank = sids - base                                      # int32[G, W]
    admitted = rank < state.admit_count[:, None]
    rank_safe = jnp.clip(rank, 0, cfg.capacity - 1)
    at = jnp.take_along_axis(state.admit_tick, rank_safe, axis=1)
    age = state.tick - at                                   # int32[G, W]

    def tiles(lags):
        groups = _lag_masks(lags)
        words = (len(lags) + 31) // 32
        out = jnp.zeros((G, sids.shape[1], words), jnp.uint32)
        for lag, mask in groups:
            cond = admitted & (age >= lag)
            out = out | jnp.where(cond[..., None], jnp.asarray(mask),
                                  jnp.uint32(0))
        return out

    return tiles(cfg.ack_lag), tiles(cfg.vote_lag), tiles(cfg.hold_lag)


# the int32 per-tick counts of pipeline_tick's out (run_pipeline stacks
# each over the scanned ticks)
COUNTERS = ("flushed", "admitted", "dropped", "requests", "ordered",
            "stable", "decided")


def pipeline_tick(cfg: PipelineConfig, state: PipelineState,
                  arrived: jax.Array, sizes: jax.Array,
                  route_table: jax.Array)\
        -> tuple[PipelineState, dict]:
    """One tick through all four stages. ``arrived``/``sizes`` are one
    row of the workload arrays (bool[C] / int32[C]); ``route_table`` is
    :func:`build_route_table` for the current epoch. Trace-safe with
    ``cfg`` static (see ``pipeline_tick_jit``).

    ``out`` holds this tick's counts at the stage boundaries, int32
    scalars (:data:`COUNTERS`), and ``overflowed`` (bool, cumulative):

    * ``flushed``: batches the batcher flushed;
    * ``admitted``: flushed batches routed to a group;
    * ``dropped``: ordered ids the merge append could not hold;
    * ``requests``: requests in the flushed batches;
    * ``ordered``: ids assigned an instance (order quorum);
    * ``stable``: ids newly stable (stability quorum of the partition);
    * ``decided``: ids newly decided (commit quorum).

    The engine counts are differences of :func:`repro.engine.api.progress`
    before and after the engine stage, so they mean the same on the
    facade, adaptive and meshed paths. With
    ``GatingConfig.fresh_stable`` a refilled slot counts as stable when
    it is made."""
    G, R, D = cfg.engine.groups, cfg.capacity, cfg.n_lanes
    before = api.progress(cfg.engine, state.engine)
    with jax.named_scope(GATHER):
        idx, mask = cfg.lane_clients()
        lane_sizes = sizes[idx].astype(jnp.int32)           # [D, K]
        lane_valid = arrived[idx] & jnp.asarray(mask)

    # stage 2: byte-budget batching, linger-0 tail flush
    bstate, fl = tick_flushes(
        state.batch, lane_sizes, lane_valid,
        budget_bytes=cfg.budget_bytes, max_requests=cfg.max_requests)

    # stage 3a: admission — flatten flushes lane-major (lane order, then
    # stream position; the order a DES tick multicasts them), route each
    # bid, and scatter admission records at per-group dense ranks
    with jax.named_scope(ADMISSION):
        fvalid = fl.valid.reshape(-1)                       # [N], N=D*(K+1)
        fseq = fl.seq.reshape(-1)
        flane = jnp.broadcast_to(
            jnp.arange(D, dtype=jnp.int32)[:, None],
            fl.valid.shape).reshape(-1)
        seq_over = fvalid & (fseq >= cfg.seq_capacity)
        fseq_safe = jnp.clip(fseq, 0, cfg.seq_capacity - 1)
        fgroup = route_table[flane, fseq_safe]              # [N]
        onehot = (fgroup[:, None] == jnp.arange(G)) & fvalid[:, None]
        onehot = onehot.astype(jnp.int32)                   # [N, G]
        prior = jnp.cumsum(onehot, axis=0) - onehot
        rank = state.admit_count[fgroup] + \
            jnp.take_along_axis(prior, fgroup[:, None], axis=1)[:, 0]
        cap_over = fvalid & (rank >= R)
        ok = fvalid & ~cap_over & ~seq_over
        g_idx = jnp.where(ok, fgroup, G)                    # G → dropped
        r_idx = jnp.clip(rank, 0, R - 1)
        admit_tick = state.admit_tick.at[g_idx, r_idx].set(
            state.tick, mode="drop")
        bid_code = state.bid_code.at[g_idx, r_idx].set(
            flane * cfg.seq_capacity + fseq, mode="drop")
        admit_count = state.admit_count + onehot.sum(axis=0)
        overflowed = state.overflowed | cap_over.any() | seq_over.any()

        state = state._replace(
            batch=bstate, admit_count=admit_count, admit_tick=admit_tick,
            bid_code=bid_code,
            flushed_bytes=state.flushed_bytes
            + jnp.where(fl.valid, fl.bytes, 0).sum(axis=1),
            n_flushed=state.n_flushed
            + fl.valid.sum(axis=1, dtype=jnp.int32),
            overflowed=overflowed)
        counts = {"flushed": fvalid.sum(dtype=jnp.int32),
                  "admitted": onehot.sum(dtype=jnp.int32),
                  "requests": jnp.where(fl.valid, fl.count, 0).sum(
                      dtype=jnp.int32)}

    # stage 3b: delivery tiles from admission ages (live slot→id map)
    with jax.named_scope(LAG_TILES):
        acks, votes, holds = _lag_tiles(cfg, state)

    # stage 4: gated ordering + merge, via the facade. With
    # EngineConfig.adaptive set, the adaptive subtick variant re-absorbs
    # the same tiles (idempotent OR) for up to K−1 extra masked
    # assignment rounds, so a group whose undecided/unstable backlog has
    # spread ahead of its peers drains at R × order_budget ids per
    # pipeline tick — size merge_capacity for up to K·max_entries
    # appended entries per tick instead of max_entries.
    if cfg.engine.adaptive is not None:
        estate, eout = adaptive_mod.subtick_pass(
            cfg.engine, state.engine, acks, votes, holds=holds)
    else:
        estate, eout = api.tick(cfg.engine, state.engine, acks, votes,
                                holds=holds)
    state = state._replace(engine=estate,
                           tick=state.tick + jnp.int32(1))
    after = api.progress(cfg.engine, estate)
    out = {**counts, "dropped": eout["dropped"],
           **{k: after[k] - before[k] for k in after},
           "overflowed": overflowed}
    return state, out


# the pipeline state (engine + admission bookkeeping) is donated: every
# tick rewrites the whole tree and callers thread the returned state, so
# the input tree is dead on return.  The workload rows and route table
# are NOT donated — feeders replay them across runs.
pipeline_tick_jit = jax.jit(pipeline_tick, static_argnames=("cfg",),
                            donate_argnums=(1,))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def run_pipeline(cfg: PipelineConfig, state: PipelineState,
                 arrived: jax.Array, sizes: jax.Array,
                 route_table: jax.Array)\
        -> tuple[PipelineState, dict]:
    """Scan :func:`pipeline_tick` over whole workload arrays
    (bool[T, C] / int32[T, C]) in one fused jit — the end-to-end hot
    loop the pipeline bench measures. Per-tick counts come back stacked:
    int32[T] for each key of :data:`COUNTERS`."""
    def step(st, xs):
        st, out = pipeline_tick(cfg, st, xs[0], xs[1], route_table)
        return st, {k: out[k] for k in COUNTERS}

    return jax.lax.scan(step, state, (arrived, sizes))


def committed(cfg: PipelineConfig, state: PipelineState)\
        -> tuple[jax.Array, jax.Array, jax.Array]:
    """(merged, merged_count, committed_count) of the pipeline's engine."""
    return api.committed_prefix(cfg.engine, state.engine)


def decode_merged(cfg: PipelineConfig, state: PipelineState,
                  merged, count) -> list[tuple[str, int]]:
    """Map the engine's merged consumable prefix back to batch bids.

    Control entries (SKIP/PAD/RECONFIG, all negative) are dropped —
    they are the engine's twin of the DES's ``__noop__`` /
    ``__reconfig__`` control bids, which learners also never execute.
    Returns ``[("d<lane>", seq), ...]`` in merged order."""
    codes = np.asarray(state.bid_code)
    stride = cfg.id_stride
    out = []
    for e in np.asarray(merged)[:int(count)]:
        e = int(e)
        if e < 0:
            continue
        g, k = divmod(e, stride)
        if not (0 <= g < codes.shape[0] and k < codes.shape[1]):
            raise ValueError(f"merged id {e} outside the admission record "
                             f"(rank {k} ≥ capacity {codes.shape[1]})")
        code = int(codes[g, k])
        if code < 0:
            raise ValueError(f"merged id {e} (group {g} rank {k}) was "
                             "never admitted")
        out.append(lane_bid(*divmod(code, cfg.seq_capacity)))
    return out


def reconfigure_pipeline(cfg: PipelineConfig, state: PipelineState,
                         old_epoch: int, new_epoch: int)\
        -> tuple[PipelineState, dict]:
    """Quiescent drain-then-switch: the facade reconfigure, plus the
    pipeline-level refusal to re-home. Rank addressing is per-row
    (id ``g·stride+k`` ↔ ``admit_tick[g, k]``), so an
    admitted-but-unordered id moved to another row would become
    unreachable by the delivery model — callers must drain (tick with
    no arrivals until every admitted batch is ordered) before
    switching, exactly like the DES admin event waits for a quiet
    boundary. Raises if the engine had to move any id."""
    estate, report = api.reconfigure(cfg.engine, state.engine,
                                     old_epoch, new_epoch)
    if int(report.get("moved", 0)) != 0:
        raise ValueError(
            f"reconfigure moved {report['moved']} in-flight ids between "
            "rows; the closed pipeline requires a drained engine at the "
            "epoch switch (no admitted-but-unordered batches)")
    return state._replace(engine=estate), report


def plan_admissions(cfg: PipelineConfig, workload: Workload,
                    route_table: np.ndarray) -> dict:
    """Host-side numpy twin of stages 1–3a: replay the workload through
    the *streaming* ``BatchAccumulator`` (one per lane, tail-flushed
    every tick) and the same route table, producing per-group admission
    records. Independent of the jit path — the pipeline tests replay
    both and require identical ranks, ticks and bid codes."""
    arrived = np.asarray(workload.arrived)
    sizes = np.asarray(workload.sizes)
    T = arrived.shape[0]
    D = cfg.n_lanes
    accs = [BatchAccumulator(cfg.budget_bytes, cfg.max_requests)
            for _ in range(D)]
    seqs = [0] * D
    admits = {g: [] for g in range(cfg.engine.groups)}

    def admit(d, t):
        s = seqs[d]
        seqs[d] += 1
        if s >= cfg.seq_capacity:
            raise ValueError(f"lane {d} overflowed seq_capacity="
                             f"{cfg.seq_capacity}")
        g = int(route_table[d, s])
        admits[g].append({"lane": d, "seq": s, "tick": t,
                          "rank": len(admits[g])})

    for t in range(T):
        closed = [0] * D                  # budget closures per lane
        for c in np.nonzero(arrived[t])[0]:
            d = int(c) % D
            if accs[d].add(int(sizes[t, c])) is not None:
                closed[d] += 1
        # jit order: lane-major, overflow closures before the lane's tail
        for d in range(D):
            for _ in range(closed[d]):
                admit(d, t)
            if accs[d].flush() is not None:
                admit(d, t)
    return admits
