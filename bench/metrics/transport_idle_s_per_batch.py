"""Seconds a batch in which the device ran nothing, under
the device transport's spans (`transport.operator`, `.pack`,
`.dispatch`, `.fetch`): host layout, uploads, the shard program's
trace, lowering and compile or cache read."""
from spanidle import idle_s_per_unit


def read(run):
    return idle_s_per_unit(run, "transport")
