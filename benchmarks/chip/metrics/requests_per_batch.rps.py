"""Requests per flushed batch over the traced segment's arrival and drain
ticks: the program's ``requests`` count over its ``flushed`` count
(``counters.py``)."""
import counters


def read(run):
    got = counters.read(run)
    if got is None:
        return None
    c = got["counts"]
    return float(c["requests"].sum() / c["flushed"].sum())
