"""Batched personalized PageRank: `repro.streaming.ppr_push_batched`.

Set-up hands the graph to a `DeltaGraph` and runs the mix's warm-up
batches, then one batch at each lane width below the mix's batch that
they did not run (the freezing driver compacts lanes to power-of-two
widths, each its own program): the window compiles nothing. The first
batch packs and uploads the layout of the graph's version. Each call of
the window is one batch of the stream through `ppr_push_batched`: one
closed loop, one batch in flight. Its answers and certificates are kept.

After the window every lane of every batch is compared with the float64
reference (`ppr_reference.py`): its L1 distance must be within the
cell's limit `l1_err`, and the program's own certificate within the
query's tolerance. A batch with a lane that misses either fails.

Under the control (bench/control.py) the program is not built: each
batch is answered by the reference's iteration at the control's lower
precision, certified exactly in float64.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

import ppr_reference

# what the window's counters read from BatchedPPRStats
COUNTERS = ("chunks", "widths", "kernel")


def _check_program():
    """Raise before the first pack where the program lacks the counters
    the metrics read."""
    from repro.streaming.incremental import BatchedPPRStats
    have = {f.name for f in dataclasses.fields(BatchedPPRStats)}
    have |= set(dir(BatchedPPRStats))
    missing = [c for c in COUNTERS if c not in have]
    if missing:
        raise RuntimeError(f"BatchedPPRStats has no {missing}: this program "
                           "cannot run the cell")


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.alpha = float(ctx.config["alpha"])
        self.tol = float(ctx.mix["tol"])
        self.stream = ctx.traffic.make(ctx.mix, ctx.graph, ctx.seed)
        self.answers = []                 # (seed sets, X, certificates)
        self.dg = None
        warmup = int(ctx.mix["warmup_batches"])
        if ctx.control is not None:
            c = ctx.control
            self.ref = ppr_reference.Reference(ctx.graph, self.alpha)
            self.lowp = ppr_reference.lowp_solver(
                ctx.graph, self.alpha, c["dtype"], tol=float(c["tol"]),
                max_iters=int(c["max_iters"]))
            for _ in range(warmup):
                next(self.stream)
            return
        _check_program()
        from repro.streaming import DeltaGraph
        from program import csr_graph

        self.args = ctx.workload["args"]
        self.dg = DeltaGraph(csr_graph(ctx.graph))
        ctx.phases.mark("delta_graph")
        seen = set()
        for _ in range(warmup):
            seen.update(w for w, _ in self.call(next(self.stream))[2].widths)
        width = 1
        while width < int(ctx.mix["batch"]):
            if width not in seen:
                self.call(next(self.stream)[:width])
            width *= 2

    def call(self, seed_sets):
        import jax
        from repro.streaming import ppr_push_batched
        with jax.profiler.TraceAnnotation("bench.ppr"):
            return ppr_push_batched(
                self.dg, seed_sets, alpha=self.alpha, tol=self.tol,
                backend=self.args["backend"], method=self.args["method"])

    def step(self) -> dict:
        sets = next(self.stream)
        if self.ctx.control is not None:
            x = self.lowp(sets)
            self.answers.append((sets, x, self.ref.certificates(sets, x)))
            return dict(units=1, iters=0, lane_iters=[0] * len(sets),
                        chunks=0, widths=[], path="control")
        x, certs, st = self.call(sets)
        self.answers.append((sets, x, certs))
        return dict(units=1, iters=int(st.iters),
                    lane_iters=[int(i) for i in st.lane_iters],
                    chunks=int(st.chunks),
                    widths=[[int(w), int(i)] for w, i in st.widths],
                    path=st.path, kernel=st.kernel,
                    cert=float(certs.max()))

    def work(self) -> dict:
        return dict(itemsize=4)

    def release(self) -> None:
        self.dg = None

    def check(self):
        limit = float(self.ctx.workload["limits"]["l1_err"])
        ref_tol = float(self.ctx.workload["ref_tol"])
        refs = ppr_reference.solve_all(
            self.ctx.graph, self.alpha, [a[0] for a in self.answers],
            tol=ref_tol, workers=len(os.sched_getaffinity(0)))
        errs, certs, failed = [], [], 0
        for (_, x, c), x_ref in zip(self.answers, refs):
            errs.append(float(np.abs(x - x_ref).sum(axis=0).max()))
            certs.append(float(np.max(c)))
            failed += errs[-1] > limit or certs[-1] > self.tol
        return {"l1_err": {"value": max(errs, default=float("inf")),
                           "limit": limit},
                "cert": {"value": max(certs, default=float("inf")),
                         "limit": self.tol}}, failed


def setup(ctx) -> Session:
    return Session(ctx)
