"""Mean solver iterations per solve (SolveResult.iters)."""
from harness import mean


def read(run):
    return mean(c["iters"] for c in run["counters"])
