"""Seconds a batch in which the device ran nothing, under the solver's
spans (`solver.prepare`, `.loop`, `.finish`, and `solver.recheck`: the
freezing driver's host recheck and lane compaction after each chunk)."""
from spanidle import idle_s_per_unit


def read(run):
    return idle_s_per_unit(run, "solver")
