"""Device supersteps per published batch, summed over the drain's
attempts (ShardedUpdateStats.supersteps)."""
from harness import mean


def read(run):
    return mean(c["supersteps"] for c in run["counters"])
