"""Device-sharded group execution: ``shard_map`` over a ``("group",)`` mesh.

The engine's G ordering groups are embarrassingly parallel within a
tick — quorum math, the stability gate, recycling and the adaptive
masked rounds are all row-wise over the leading group axis (``vmap``
inside, no cross-group term).  The only cross-group computation is the
round-robin merge: the uniform SKIP-pad width of a lock-step tick is
``min(max_g n_assigned[g], max_entries)`` (a cross-group max), and the
log itself interleaves all groups.  This module exploits exactly that
split:

* **state is sharded**: every leaf of the family core state
  (QuorumState / RecycleState / GatedRecycleState / DissemState), the
  slot→id map and the per-group traffic tiles partition their leading
  group axis across a 1-D ``("group",)`` device mesh
  (``launch.mesh.make_group_mesh``) — per-group work runs
  device-parallel with **zero cross-device traffic**;
* **the merge is replicated**: each device extracts its local groups'
  fixed-width entry rows (:func:`merge.round_entries` — per-group math,
  no cross-group term), one ``all_gather`` per pass collects the
  ``[G, width]`` block plus the per-group assignment counts, and every
  device then applies the *same* wide ``append_entries`` to its replica
  of the MergeState — reproducing the lock-step merge byte for byte
  (the uniform count is recomputed from the gathered ``n_assigned``,
  the same cross-group max the unmeshed path takes).

Because all engine math is integer/boolean (no float reassociation),
the meshed path is **bit-identical** to the unmeshed one for any device
count — ``tests/test_multidevice.py`` pins 1 device ≡ 8 emulated
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) for
all four families, through mid-run recycles and epoch reconfigs.

Padding: when the clamped mesh size does not divide G, the group axis
is padded (inside this module only — facade state stays logical-G) with
freshly initialized rows: nothing is admitted in them and they receive
zero traffic, so they never assign, never recycle, and are sliced off
the gathered entries *before* the merge append.  Physical rows never
move between devices, which is why recycling (pure row-local
compaction) and epoch reconfiguration (host-side ``np.array`` gathers
the sharded rows, rebuilt arrays re-shard at the next jitted call) keep
working unchanged.

Entry points mirror the facade verbs and are reached through it
(``EngineConfig(mesh=MeshConfig(...))``): :func:`run` (+ donating
:data:`run_jit`) behind ``api.run``, :func:`tick` behind ``api.tick``
(and hence the pipeline's engine stage), :func:`adaptive_pass` /
:func:`subtick_pass` behind their ``engine.adaptive`` twins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dissem.engine import init_dissem
from ..launch import mesh as launch_mesh
from . import adaptive as adaptive_mod
from . import merge as merge_mod
from . import sharded as sharded_mod


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the merge replica
    is rebuilt from ``all_gather`` results, which the checker cannot
    prove replicated."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=None)
def _cached_mesh(groups, n_devices, axis_name, n_avail):
    # n_avail keys the cache so a changed device topology (impossible
    # mid-process today, cheap insurance anyway) cannot serve a stale mesh
    return launch_mesh.make_group_mesh(groups, n_devices=n_devices,
                                       axis_name=axis_name)


def _mesh_for(cfg):
    return _cached_mesh(cfg.groups, cfg.mesh.n_devices,
                        cfg.mesh.axis_name, len(jax.devices()))


# -- group-axis padding -------------------------------------------------------

def _cat0(a, b):
    return jax.tree.map(lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def _fresh_rows(cfg, pad):
    """``pad`` inert group rows: fresh family state (nothing admitted,
    nothing stable) whose zero traffic keeps it inert forever — the
    merge-facing outputs of these rows are sliced off before any
    append, so their (colliding, never-emitted) slot ids are moot."""
    W, D, S = cfg.window, cfg.n_diss, cfg.n_seq
    fam = cfg.family
    if fam in ("plain", "gated"):
        core = sharded_mod.init_sharded(pad, W, D, S)
        dissem = None if fam == "plain" else init_dissem(
            pad, W, cfg.gating.n_diss_partition,
            pre_stable=cfg.gating.pre_stable)
        return core, dissem, sharded_mod.default_slot_ids(pad, W)
    if fam == "recycled":
        core = sharded_mod.init_recycled(
            pad, W, D, S, id_stride=cfg.recycling.id_stride)
        return core, None, None
    core = sharded_mod.init_gated_recycled(
        pad, W, D, S, n_diss_partition=cfg.gating.n_diss_partition,
        id_stride=cfg.recycling.id_stride,
        pre_stable=cfg.gating.pre_stable)
    return core, None, None


def _pad_state(cfg, state, pad):
    """(core, dissem, slot_ids) with ``pad`` inert rows appended."""
    if pad == 0:
        return state.core, state.dissem, state.slot_ids
    pcore, pdissem, psids = _fresh_rows(cfg, pad)
    return (_cat0(state.core, pcore),
            None if state.dissem is None else _cat0(state.dissem, pdissem),
            None if state.slot_ids is None
            else _cat0(state.slot_ids, psids))


def _unpad(tree, pad, n):
    if pad == 0 or tree is None:
        return tree
    return jax.tree.map(lambda x: x[:n], tree)


def _pad_zeros(x, pad, axis):
    """Zero rows along ``axis`` (traffic tiles for the inert pad rows)."""
    if pad == 0 or x is None:
        return x
    def f(a):
        shape = list(a.shape)
        shape[axis] = pad
        return jnp.concatenate([a, jnp.zeros(shape, a.dtype)], axis=axis)
    return jax.tree.map(f, x)


# -- the merge crossing -------------------------------------------------------

def _local_id_base(cfg, rows, axis):
    """Fresh-id range bases for this device's ``rows`` local group rows.

    The recycled families mint fresh instance ids from per-group ranges
    ``logical_group * id_stride``; inside a shard, local row 0 is
    logical group ``axis_index * rows``, so the default row-position
    base in ``sharded.recycle_groups`` would hand device d>0 the wrong
    (and colliding) ranges.  Pad rows get out-of-range bases, which is
    fine — they never recycle (zero traffic, free == W ≥ watermark)."""
    if cfg.recycling is None:
        return None
    first = jax.lax.axis_index(axis) * rows
    return ((first + jnp.arange(rows, dtype=jnp.int32))
            * cfg.recycling.id_stride)


def _tick_and_append(cfg, core, dissem, slot_ids, ms, a, v, h, axis):
    """One lock-step tick on this device's rows + the replicated append.

    Local: family tick (absorb → assign → vote → recycle) and the
    fixed-width entry extraction.  Cross-device: one ``all_gather`` of
    the entry rows and assignment counts; the uniform SKIP-pad width is
    then recomputed from the *gathered* counts — the same
    ``min(max_g n_assigned, max_entries)`` the unmeshed
    ``entries_from_assigned`` takes, so the appended block is
    bit-identical.  Returns (core', dissem', ms', assigned local,
    dropped scalar — both replicated-side values computed identically
    on every device)."""
    G, K = cfg.groups, cfg.max_entries
    rows = jax.tree.leaves(core)[0].shape[0]
    ncore, ndissem, assigned, sids = adaptive_mod._family_tick(
        cfg, core, dissem, slot_ids, a, v, h,
        id_base=_local_id_base(cfg, rows, axis))
    ent_l, n_l, _ = merge_mod.round_entries(assigned, sids, K)
    ent = jax.lax.all_gather(ent_l, axis, axis=0, tiled=True)[:G]
    n_as = jax.lax.all_gather(n_l, axis, axis=0, tiled=True)[:G]
    counts = jnp.broadcast_to(jnp.minimum(jnp.max(n_as), K),
                              (G,)).astype(jnp.int32)
    dropped = jnp.sum(jnp.maximum(n_as - K, 0), dtype=jnp.int32)
    ms = merge_mod.append_entries(ms, ent, counts)
    return ncore, ndissem, ms, assigned, dropped


def _commit_gate(cfg, core, ms, axis):
    """(merged, merged_count, committed_count), replicated.

    The per-slot decided→instance scatter is row-local; the gathered
    [G, L] flags feed the same recycle-aware ``committed_prefix_len``
    the unmeshed gates use."""
    G, L = cfg.groups, ms.logs.shape[1]
    if cfg.recycling is not None:
        rs = core.rs if cfg.family == "gated_recycled" else core
        live_l = sharded_mod._decided_by_instance(rs.q.instance,
                                                  rs.q.decided, L)
        live = jax.lax.all_gather(live_l, axis, axis=0, tiled=True)[:G]
        retired = jax.lax.all_gather(rs.retired, axis, axis=0,
                                     tiled=True)[:G]
        merged, count = merge_mod.merged_prefix(ms)
        committed = merge_mod.committed_prefix_len(ms, live,
                                                   retired_base=retired)
        return merged, count, committed
    dec_l = sharded_mod._decided_by_instance(core.instance, core.decided, L)
    dec = jax.lax.all_gather(dec_l, axis, axis=0, tiled=True)[:G]
    merged, count = merge_mod.merged_prefix(ms)
    committed = merge_mod.committed_prefix_len(ms, dec)
    return merged, count, committed


# -- facade entry points ------------------------------------------------------

def run(cfg, state, acks_seq, votes_seq, holds_seq=None):
    """Device-sharded twin of ``api.run``: one ``shard_map`` wraps the
    whole T-tick scan plus the final commit gate, so state never leaves
    the devices between ticks — per tick the only collective is the
    entry-row ``all_gather``.  Same contract and return values as
    ``api.run``, merged output bit-identical for any device count."""
    mesh = _mesh_for(cfg)
    axis = cfg.mesh.axis_name
    G = cfg.groups
    pad = launch_mesh.group_padding(G, mesh)
    core, dissem, sids = _pad_state(cfg, state, pad)
    a_seq = _pad_zeros(acks_seq, pad, 1)
    v_seq = _pad_zeros(votes_seq, pad, 1)
    h_seq = _pad_zeros(holds_seq, pad, 1)

    def body(core, dissem, sids, ms, a_seq, v_seq, h_seq):
        def step(carry, tv):
            core, dissem, ms, dropped = carry
            a, v, h = tv
            core, dissem, ms, _, d_t = _tick_and_append(
                cfg, core, dissem, sids, ms, a, v, h, axis)
            return (core, dissem, ms, dropped + d_t), ()

        (core, dissem, ms, dropped), _ = jax.lax.scan(
            step, (core, dissem, ms, jnp.int32(0)),
            (a_seq, v_seq, h_seq))
        merged, count, committed = _commit_gate(cfg, core, ms, axis)
        return core, dissem, ms, merged, count, committed, dropped

    f = _shard_map(
        body, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(),
                  P(None, axis), P(None, axis), P(None, axis)),
        out_specs=(P(axis), P(axis), P(), P(), P(), P(), P()))
    core, dissem, ms, merged, count, committed, dropped = f(
        core, dissem, sids, state.merge, a_seq, v_seq, h_seq)
    jax.debug.callback(sharded_mod._assert_no_dropped, dropped)
    state = state._replace(core=_unpad(core, pad, G),
                           dissem=_unpad(dissem, pad, G), merge=ms)
    return state, merged, count, committed


# state (arg 1, merge log included) is donated: the scan rewrites the
# whole tree, callers thread the returned state — and the facade's only
# meshed multi-tick path goes through here, so per-pass copies are gone
run_jit = jax.jit(run, static_argnames=("cfg",), donate_argnums=(1,))


def tick(cfg, state, acks, votes, holds=None):
    """Device-sharded twin of ``api.tick`` (trace-safe, ``cfg`` static;
    the pipeline's engine stage reaches it through the facade).  The
    out dict is reduced to what crosses devices for free:
    ``assigned`` (gathered, [G, W]) and ``dropped``."""
    mesh = _mesh_for(cfg)
    axis = cfg.mesh.axis_name
    G = cfg.groups
    pad = launch_mesh.group_padding(G, mesh)
    core, dissem, sids = _pad_state(cfg, state, pad)
    a = _pad_zeros(acks, pad, 0)
    v = _pad_zeros(votes, pad, 0)
    h = _pad_zeros(holds, pad, 0)

    def body(core, dissem, sids, ms, a, v, h):
        core, dissem, ms, assigned, dropped = _tick_and_append(
            cfg, core, dissem, sids, ms, a, v, h, axis)
        assigned = jax.lax.all_gather(assigned, axis, axis=0,
                                      tiled=True)[:G]
        return core, dissem, ms, assigned, dropped

    f = _shard_map(
        body, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(),
                  P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(), P(), P()))
    core, dissem, ms, assigned, dropped = f(core, dissem, sids,
                                            state.merge, a, v, h)
    state = state._replace(core=_unpad(core, pad, G),
                           dissem=_unpad(dissem, pad, G), merge=ms)
    return state, {"assigned": assigned, "dropped": dropped}


def adaptive_pass(cfg, state, queue):
    """Device-sharded twin of ``adaptive.adaptive_pass`` (reached through
    it; the donating ``adaptive_pass_jit`` wrapper applies unchanged).

    The queue shards with its groups; the masked fixed-K round loop
    (:func:`adaptive._masked_rounds_core`, shape-polymorphic in the row
    axis) runs on local rows.  Two things cross devices: the lag/need
    vectors feeding the uniform round count R (gathered, then sliced to
    the logical G so pad rows cannot distort the spread), and the
    [G, K·rw] entry buffer for the replicated wide append."""
    ad = cfg.adaptive
    mesh = _mesh_for(cfg)
    axis = cfg.mesh.axis_name
    G, rw = cfg.groups, cfg.max_entries
    pad = launch_mesh.group_padding(G, mesh)
    core, dissem, sids = _pad_state(cfg, state, pad)
    qa = _pad_zeros(queue.acks, pad, 0)
    qv = _pad_zeros(queue.votes, pad, 0)
    qh = _pad_zeros(queue.holds, pad, 0)
    qhead = _pad_zeros(queue.head, pad, 0)
    qtail = _pad_zeros(queue.tail, pad, 0)

    def body(core, dissem, sids, ms, qa, qv, qh, qhead, qtail):
        rem = qtail - qhead                                  # local rows
        lag_l = rem if ad.policy == "backlog" else \
            adaptive_mod._state_lag(cfg, core, dissem, ad.policy)
        need_l = (rem > 0) | (adaptive_mod._assignable(
            adaptive_mod._quorum(cfg, core)) > 0)
        lag = jax.lax.all_gather(lag_l, axis, axis=0, tiled=True)[:G]
        need = jax.lax.all_gather(need_l, axis, axis=0, tiled=True)[:G]
        R = adaptive_mod._rounds_from_spread(ad, lag)
        R = jnp.where(jnp.any(need), R, 0).astype(jnp.int32)
        k = jnp.minimum(R, rem).astype(jnp.int32)
        C = qa.shape[1]
        g = jnp.arange(qa.shape[0])

        def tile_fn(j, consume):
            slot = (qhead + j) % C
            def take(buf):
                m = consume.reshape((-1,) + (1,) * (buf.ndim - 2))
                return jnp.where(m, buf[g, slot], jnp.uint32(0))
            return (take(qa), take(qv),
                    None if qh is None else take(qh))

        rows = jax.tree.leaves(core)[0].shape[0]
        core, dissem, buf, dropped_l = adaptive_mod._masked_rounds_core(
            cfg, core, dissem, sids, R, tile_fn, lambda j: j < k,
            id_base=_local_id_base(cfg, rows, axis))
        buf_g = jax.lax.all_gather(buf, axis, axis=0, tiled=True)[:G]
        counts = jnp.broadcast_to(R * rw, (G,)).astype(jnp.int32)
        ms = merge_mod.append_entries(ms, buf_g, counts)
        dropped = jax.lax.psum(dropped_l, axis)
        consumed = jax.lax.all_gather(k, axis, axis=0, tiled=True)[:G]
        return core, dissem, ms, qhead + k, R, consumed, dropped

    f = _shard_map(
        body, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(),
                  P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(), P(axis), P(), P(), P()))
    core, dissem, ms, head, R, consumed, dropped = f(
        core, dissem, sids, state.merge, qa, qv, qh, qhead, qtail)
    state = state._replace(core=_unpad(core, pad, G),
                           dissem=_unpad(dissem, pad, G), merge=ms)
    queue = queue._replace(head=_unpad(head, pad, G))
    return state, queue, {"rounds": R, "consumed": consumed,
                          "dropped": dropped}


def subtick_pass(cfg, state, acks, votes, holds=None):
    """Device-sharded twin of ``adaptive.subtick_pass`` (the queue-less
    pipeline wiring; reached through it).  Same masked-round machinery
    as :func:`adaptive_pass` with the pipeline's single rebuilt tile
    set re-absorbed each round and every group consuming round 0."""
    ad = cfg.adaptive
    mesh = _mesh_for(cfg)
    axis = cfg.mesh.axis_name
    G, rw = cfg.groups, cfg.max_entries
    pad = launch_mesh.group_padding(G, mesh)
    core, dissem, sids = _pad_state(cfg, state, pad)
    a = _pad_zeros(acks, pad, 0)
    v = _pad_zeros(votes, pad, 0)
    h = _pad_zeros(holds, pad, 0)
    policy = "undecided" if ad.policy == "backlog" else ad.policy

    def body(core, dissem, sids, ms, a, v, h):
        lag_l = adaptive_mod._state_lag(cfg, core, dissem, policy)
        lag = jax.lax.all_gather(lag_l, axis, axis=0, tiled=True)[:G]
        R = adaptive_mod._rounds_from_spread(ad, lag)
        rows = jax.tree.leaves(core)[0].shape[0]

        def tile_fn(j, consume):
            return a, v, h

        core, dissem, buf, dropped_l = adaptive_mod._masked_rounds_core(
            cfg, core, dissem, sids, R, tile_fn,
            lambda j: jnp.full((rows,), j == 0),
            id_base=_local_id_base(cfg, rows, axis))
        buf_g = jax.lax.all_gather(buf, axis, axis=0, tiled=True)[:G]
        counts = jnp.broadcast_to(R * rw, (G,)).astype(jnp.int32)
        ms = merge_mod.append_entries(ms, buf_g, counts)
        dropped = jax.lax.psum(dropped_l, axis)
        return core, dissem, ms, R, dropped

    f = _shard_map(
        body, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(),
                  P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(), P(), P()))
    core, dissem, ms, R, dropped = f(core, dissem, sids, state.merge,
                                     a, v, h)
    state = state._replace(core=_unpad(core, pad, G),
                           dissem=_unpad(dissem, pad, G), merge=ms)
    return state, {"rounds": R, "dropped": dropped}
