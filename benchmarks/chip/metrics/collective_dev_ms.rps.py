"""Mesh crossing: device ms of the collectives in ``run_pipeline``'s module
(all-gathers, all-reduces and the like, the partitioner's own among them;
``collectives.py``), per arrival tick of the traced segment, averaged over
the device planes. The commit gate's collectives count in
``commit_gate_dev_ms.rps``."""
from collectives import ms_per_tick


def read(run):
    return ms_per_tick(run)
