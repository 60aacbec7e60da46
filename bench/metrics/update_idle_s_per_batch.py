"""Seconds a batch in which the device ran nothing, under
`update.apply_delta`: the graph's delta, the residual's seed and the
uniform fold."""
from spanidle import idle_s_per_unit


def read(run):
    return idle_s_per_unit(run, "update")
