"""Lane-iterations a batch: the sum over its queries of the iterations
each lane ran before it froze (BatchedPPRStats.lane_iters)."""
from harness import mean


def read(run):
    return mean(sum(c["lane_iters"]) for c in run["counters"])
