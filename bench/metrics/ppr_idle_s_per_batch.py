"""Seconds a batch in which the device ran nothing, under the personalized
query's host spans (`ppr.stack`: seed validation and the teleport stack;
`ppr.certify`: the exact float64 residual of every lane)."""
from spanidle import idle_s_per_unit


def read(run):
    return idle_s_per_unit(run, "ppr")
