"""Compiles for a described TPU v5e: the main path's kernels and one
pipeline tick at the paper's width pass the chip's compiler.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the TPU compiler (Mosaic for the Pallas kernels)
compiles against it, refusing what the chip would refuse — block shapes
off the (8, 128) tiling, layouts that disagree with XLA's, programs that
do not fit. Nothing runs, so results are checked elsewhere
(``tests/test_kernels.py`` in interpret mode, ``chip_smoke.py`` on the
chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around these
compiles: an entry written for a described chip cannot be read back
without one.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.kernels.dissem import stability_update_grouped  # noqa: E402
from repro.kernels.quorum import (quorum_update,  # noqa: E402
                                  quorum_update_grouped)
from repro.pipeline import init_pipeline, pipeline_tick_jit  # noqa: E402

W = 2048
REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNEL_CASES = [("quorum_update", 1)] + [
    (name, g) for name in ("quorum_update_grouped",
                           "stability_update_grouped") for g in (1, 4)]


@pytest.mark.parametrize("words", [8, 32])
@pytest.mark.parametrize("name,groups", KERNEL_CASES)
def test_kernel_compiles_for_v5e(one_chip, name, groups, words):
    """Mosaic accepts the kernel at W=2048; WORDS=8 is a 250-node
    partition, WORDS=32 the 1000 disseminators of §5."""
    fn = {"quorum_update": quorum_update,
          "quorum_update_grouped": quorum_update_grouped,
          "stability_update_grouped": stability_update_grouped}[name]
    lead = () if name == "quorum_update" else (groups,)
    args = (_spec(lead + (W, words), jnp.uint32, one_chip),
            _spec(lead + (W, words), jnp.uint32, one_chip),
            _spec(lead + (W,), jnp.bool_, one_chip))
    compiled = jax.jit(
        lambda b, u, s: fn(b, u, s, majority=126, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pipeline_tick_compiles_at_paper_width(one_chip):
    """One ``pipeline_tick`` of the deployment ``chip_smoke.py`` runs:
    m=1000 in G=4 partitions, s=20, 10⁵ clients, window 2048."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    pcfg = smoke.paper_config(0)
    state = jax.tree.map(lambda x: _spec(x.shape, x.dtype, one_chip),
                         jax.eval_shape(lambda: init_pipeline(pcfg)))
    C = pcfg.n_clients
    compiled = pipeline_tick_jit.lower(
        pcfg, state, _spec((C,), jnp.bool_, one_chip),
        _spec((C,), jnp.int32, one_chip),
        _spec((pcfg.n_lanes, pcfg.seq_capacity), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < 16 * 2**30
