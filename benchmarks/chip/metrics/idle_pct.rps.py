"""Device idle share of the traced phase: 100 × (1 − busy / window), busy
being the union of the device operations' intervals (``devtrace.reduce``)."""


def read(run):
    return None if run.trace is None else run.trace["idle_pct"]
