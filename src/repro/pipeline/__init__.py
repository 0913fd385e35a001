"""Closed in-jax pipeline: workload → batcher → stability → ordering.

The four decoupled HT-Paxos stages (§4.1) as one jit-compiled loop —
``repro.pipeline.closed.pipeline_tick`` — driven by pre-drawn client
workload arrays (``workload``), through a ``lax.scan``-able port of the
byte-budget batcher (``vbatch``), a per-node lag delivery model, and
the gated ordering engine behind the ``repro.engine.api`` facade.

See :mod:`repro.pipeline.closed` for the stage-by-stage story and the
rank-addressing scheme that keeps the delivery model exact across
window recycling and drain-then-switch reconfiguration. ``STAGES`` names
the tick's stages as ``jax.named_scope`` scopes; ``COUNTERS`` names the
per-tick counts ``run_pipeline`` returns.
"""
from ..engine.stages import STAGES
from .closed import (COUNTERS, PipelineConfig, PipelineState,
                     build_route_table, committed, decode_merged,
                     init_pipeline, lane_bid, pipeline_tick,
                     pipeline_tick_jit, plan_admissions,
                     reconfigure_pipeline, run_pipeline)
from .vbatch import BatchState, TickFlushes, batch_step, init_batch_state, \
    tick_flushes
from .workload import Workload, WorkloadModel

__all__ = [
    "COUNTERS", "STAGES", "PipelineConfig", "PipelineState",
    "build_route_table", "committed", "decode_merged", "init_pipeline",
    "lane_bid", "pipeline_tick", "pipeline_tick_jit", "plan_admissions",
    "reconfigure_pipeline", "run_pipeline",
    "BatchState", "TickFlushes", "batch_step", "init_batch_state",
    "tick_flushes",
    "Workload", "WorkloadModel",
]
