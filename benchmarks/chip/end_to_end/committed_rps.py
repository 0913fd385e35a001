"""Requests whose batch is in the committed log, over all the window's wall
time (the final drain included, so every admitted request is counted)."""


def read(run):
    return run.committed_requests / run.window_s
