"""Workload gather (``ht.gather``): client rows gathered to their lanes'
request slots. Device ms per arrival tick of the traced segment: the ops of
``run_pipeline``'s module whose innermost stage scope is this one
(``scopes.py``), over the segment's arrival ticks."""
from scopes import stage_ms_per_tick


def read(run):
    return stage_ms_per_tick(run, "ht.gather")
