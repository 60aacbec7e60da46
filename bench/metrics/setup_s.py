"""Seconds from process start to the window: generation, load, pack,
upload, compile and warm-up."""


def read(run):
    return run["setup_s"]
