"""Stage names of the closed pipeline's tick, as ``jax.named_scope`` scopes.

Every stage of :func:`repro.pipeline.pipeline_tick`, including the engine
stages it reaches through the facade, runs inside
``jax.named_scope(<stage>)``. The name then appears in the ``op_name``
metadata of each op the stage compiles to, and so in a profiler trace of
the device: a stage's device time can be read by name, whatever the
compiler calls its fusions.

The names carry the ``ht.`` prefix, which no path component that JAX
writes itself (``jit(...)``, ``while``, ``body``, ``cond``, ``vmap(...)``)
can match. Scopes write op metadata only; the compiled computation is the
same with or without them.

``STAGES`` is re-exported as ``repro.pipeline.STAGES``. This module imports
nothing, so every layer of the engine can use it.
"""
PREFIX = "ht."

GATHER = "ht.gather"              # client rows gathered to lane slots
BATCHER = "ht.batcher"            # byte-budget batching, tail flush
ADMISSION = "ht.admission"        # route, rank and record flushed batches
LAG_TILES = "ht.lag_tiles"        # ack/vote/hold tiles from admission ages
STABILITY = "ht.stability"        # holds absorbed, stability quorum
ORDERING = "ht.ordering"          # votes gated, order and commit quorums
MERGE_APPEND = "ht.merge_append"  # ordered ids into the round-robin log
RECYCLE = "ht.recycle"            # decided prefix retired, slots refilled
COMMIT_GATE = "ht.commit_gate"    # merged log cut at the first uncommitted

STAGES = (GATHER, BATCHER, ADMISSION, LAG_TILES, STABILITY, ORDERING,
          MERGE_APPEND, RECYCLE, COMMIT_GATE)
