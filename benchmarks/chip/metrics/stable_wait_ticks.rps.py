"""Mean ticks from a batch's admission to its stability (a majority of its
group's disseminator partition holds it, so sequencers' votes on it
count), over the traced segment's batches, from the program's per-tick
``admitted`` and ``stable`` counts (``counters.py``). Beside
``order_wait_ticks.rps`` and ``decide_wait_ticks.rps`` it tells whether a
batch waits for the holds or for the votes."""
from counters import mean_wait


def read(run):
    return mean_wait(run, "stable")
