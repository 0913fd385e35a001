"""Mean ticks from a batch's admission to its decision (the commit quorum
of votes, counted only once the batch is stable), over the traced
segment's batches, from the program's per-tick ``admitted`` and
``decided`` counts (``counters.py``)."""
from counters import mean_wait


def read(run):
    return mean_wait(run, "decided")
