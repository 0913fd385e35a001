"""Byte-budget batcher (``repro.pipeline.vbatch.tick_flushes``): host-clock
ms per call on one of the cell's rows gathered to its lanes and the live
batcher state, synced."""
from functools import partial


def read(run):
    import jax
    import jax.numpy as jnp
    from repro.pipeline import tick_flushes
    cfg = run.program.cfg
    idx, mask = cfg.lane_clients()
    arrived, sizes = run.traffic.tick(run.segment_key, run.next_tick)
    lane_sizes = sizes[idx]
    lane_valid = arrived[idx] & jnp.asarray(mask)
    batch = run.state.batch
    flushes = jax.jit(partial(tick_flushes, budget_bytes=cfg.budget_bytes,
                              max_requests=cfg.max_requests))
    return run.per_call_ms(
        lambda: lambda: flushes(batch, lane_sizes, lane_valid))
