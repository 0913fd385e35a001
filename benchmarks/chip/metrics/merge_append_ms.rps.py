"""Round-robin merge log (``repro.engine.merge.append_entries``): host-clock
ms per append into the live merge log at the cell's log capacity, each
group appending as many ids as it has admitted per tick so far, synced."""


def read(run):
    import jax
    import jax.numpy as jnp
    from repro.engine.merge import append_entries
    cfg = run.program.cfg.engine
    merge = run.state.engine.merge
    G, K = cfg.groups, cfg.max_entries
    counts = jnp.minimum(run.state.admit_count // max(run.next_tick, 1), K)
    entries = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (G, K))
    append = jax.jit(append_entries)
    return run.per_call_ms(lambda: lambda: append(merge, entries,
                                                  counts.astype(jnp.int32)))
