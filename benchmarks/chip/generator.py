"""Seeded client traffic for the benchmark, drawn on the device.

``draw`` is a copy of ``repro.pipeline.workload.WorkloadModel.draw``, kept
here so that the traffic a cell offers cannot change with the program:
Bernoulli arrivals at ``arrival_rate`` per client-tick, payload sizes drawn
from ``size_choices`` with ``size_probs`` weights.

Rows are addressed by (segment, tick): the row of tick ``t`` in log segment
``s`` is ``draw(fold_in(fold_in(key(seed), s), t), 1)[0]``, so a chunk of
ticks, a single tick and the whole segment replayed for the reference after
the window are the same rows whichever way they were cut.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def draw(key, ticks: int, n_clients: int, arrival_rate: float,
         size_choices: tuple, size_probs: tuple | None):
    """``ticks`` rows of traffic from one key: (arrived bool[T, C],
    sizes int32[T, C], 0 where nothing arrived)."""
    k_arr, k_size = jax.random.split(key)
    shape = (ticks, n_clients)
    arrived = jax.random.uniform(k_arr, shape) < arrival_rate
    choices = jnp.asarray(size_choices, jnp.int32)
    if size_probs is None:
        idx = jax.random.randint(k_size, shape, 0, len(choices))
    else:
        logits = jnp.log(jnp.asarray(size_probs))
        idx = jax.random.categorical(k_size, logits, shape=shape)
    sizes = jnp.where(arrived, choices[idx], 0).astype(jnp.int32)
    return arrived, sizes


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits are kept)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


class Traffic:
    """One traffic mix (a ``traffic/<mix>.json`` file) over ``n_clients``."""

    def __init__(self, mix: dict, n_clients: int):
        self.n_clients = int(n_clients)
        self.arrival_rate = float(mix["arrival_rate"])
        self.size_choices = tuple(int(s) for s in mix["size_choices"])
        probs = mix.get("size_probs")
        self.size_probs = None if probs is None else tuple(
            float(p) for p in probs)
        self.chunk_ticks = int(mix["chunk_ticks"])
        period = mix.get("period_ms")
        self.period_s = None if period is None else float(period) / 1e3
        if not 0.0 <= self.arrival_rate <= 1.0:
            raise ValueError(f"arrival_rate {self.arrival_rate} outside [0, 1]")
        if min(self.size_choices) <= 0:
            raise ValueError("size_choices must be positive: a zero size "
                             "would read as no arrival")
        self.rows = jax.jit(self._rows, static_argnums=(2,))
        self.row = jax.jit(self._row)

    def _row(self, seg_key, t):
        a, s = draw(jax.random.fold_in(seg_key, t), 1, self.n_clients,
                    self.arrival_rate, self.size_choices, self.size_probs)
        return a[0], s[0]

    def _rows(self, seg_key, start, ticks: int):
        t = start + jnp.arange(ticks, dtype=jnp.uint32)
        return jax.vmap(self._row, in_axes=(None, 0))(seg_key, t)

    @staticmethod
    def segment_key(key, segment: int):
        return jax.random.fold_in(key, np.uint32(segment))

    def chunk(self, seg_key, start: int, ticks: int | None = None):
        """Rows of ticks ``start .. start+ticks-1`` (default: one chunk)."""
        return self.rows(seg_key, np.uint32(start),
                         self.chunk_ticks if ticks is None else ticks)

    def tick(self, seg_key, t: int):
        """Row of tick ``t``: (arrived bool[C], sizes int32[C])."""
        return self.row(seg_key, np.uint32(t))

    def segment_sizes(self, seg_key, ticks: int):
        """The segment's first ``ticks`` rows of sizes on the host,
        int32[ticks, C] (0 = no arrival), drawn a chunk at a time."""
        block = self.chunk_ticks
        out = np.zeros((ticks, self.n_clients), np.int32)
        for start in range(0, ticks, block):
            n = min(block, ticks - start)
            _, s = self.chunk(seg_key, start)
            out[start:start + n] = np.asarray(s)[:n]
        return out
