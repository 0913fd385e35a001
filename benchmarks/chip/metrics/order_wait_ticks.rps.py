"""Mean ticks from a batch's admission to its ordering (an instance
assigned on the order quorum), over the traced segment's batches, from
the program's per-tick ``admitted`` and ``ordered`` counts
(``counters.py``)."""
from counters import mean_wait


def read(run):
    return mean_wait(run, "ordered")
