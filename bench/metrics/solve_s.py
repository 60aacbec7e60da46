"""Seconds per certified solve: the whole window over the solves it
completed."""


def read(run):
    return run["window_s"] / run["units"] if run["units"] else None
