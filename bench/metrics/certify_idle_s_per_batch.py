"""Seconds a batch in which the device ran nothing, under
`certify.exact_residual`: the host's exact O(nnz) residual that
certifies each drain attempt."""
from spanidle import idle_s_per_unit


def read(run):
    return idle_s_per_unit(run, "certify")
