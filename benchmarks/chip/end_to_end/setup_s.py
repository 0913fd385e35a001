"""Seconds from process start to the start of the window: imports, the
deployment's build, the route table, compiling or loading every program
the cell uses, and one warm-up call of each."""


def read(run):
    return run.setup_s
