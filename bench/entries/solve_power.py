"""The batch solver: `repro.core.solve_power` on the resident graph.

Set-up builds the program's operator and runs one solve, which packs the
backend's layout, uploads it and compiles the fused loop. Each call of the
window is one full solve from the uniform start; its answer is kept. The
cell's traffic is that repetition alone, so the entry reads no mix. After
the window every answer is compared with the float64 reference: the L1
distance of each must be within the cell's limit `l1_err`.

Under the control (bench/control.py) the program is not built: each call
returns the reference at the control's lower precision in its place.
"""
from __future__ import annotations

import types

import reference

USES_TRAFFIC = False


class Session:
    def __init__(self, ctx):
        import jax.numpy as jnp

        self.ctx = ctx
        self.alpha = float(ctx.config["alpha"])
        args = ctx.workload["args"]
        self.itemsize = jnp.dtype(args["dtype"]).itemsize
        self.answers = []
        self.op = None
        if ctx.control is not None:
            return
        from repro.graph.csr import TransitionT
        from repro.graph.google import GoogleOperator
        from program import csr_graph

        self.kw = dict(backend=args["backend"], tol=float(args["tol"]),
                       dtype=jnp.dtype(args["dtype"]),
                       max_iters=int(args["max_iters"]))
        self.op = GoogleOperator(pt=TransitionT.from_graph(
            csr_graph(ctx.graph)), alpha=self.alpha)
        ctx.phases.mark("operator")
        self.call()                     # pack, upload, compile

    def call(self):
        import jax
        if self.ctx.control is not None:
            return self.control_solve()
        from repro.core import solve_power
        with jax.profiler.TraceAnnotation("bench.solve_power"):
            return solve_power(self.op, **self.kw)

    def control_solve(self):
        c = self.ctx.control
        x = reference.pagerank_lowp(self.ctx.graph, self.alpha, c["dtype"],
                                    tol=float(c["tol"]),
                                    max_iters=int(c["max_iters"]))
        return types.SimpleNamespace(x=x, iters=0, resid_l1=float("nan"))

    def step(self) -> dict:
        res = self.call()
        self.answers.append(res.x)
        return dict(units=1, iters=int(res.iters),
                    resid=float(res.resid_l1))

    def work(self) -> dict:
        return dict(nv=1, itemsize=self.itemsize)

    def release(self) -> None:
        self.op = None

    def check(self):
        limit = float(self.ctx.workload["limits"]["l1_err"])
        x_ref = reference.pagerank(self.ctx.graph, self.alpha,
                                   tol=float(self.ctx.workload["ref_tol"]))
        errs = [float(reference.l1(x, x_ref)) for x in self.answers]
        failed = sum(e > limit for e in errs)
        return {"l1_err": {"value": max(errs, default=float("inf")),
                           "limit": limit}}, failed


def setup(ctx) -> Session:
    return Session(ctx)
