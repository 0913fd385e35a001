"""Commit gate (``pipeline.committed``, compiled alone as ``jit_committed``;
its ops carry the scope ``ht.commit_gate``): device ms per read, the gate's
ops in that program's runs in the traced segment's drain over the number of
runs (``scopes.py``). Where the executable names no scope, because the
persistent compile cache held the same program compiled without it, the
whole module is the gate."""
from scopes import gate_ms_per_read


def read(run):
    return gate_ms_per_read(run)
