"""Seconds per published batch: the whole window over the batches it
published (a closed loop, so the mean ingest-to-publish lag)."""


def read(run):
    return run["window_s"] / run["units"] if run["units"] else None
