"""Crawl refresh: `repro.streaming.RankServer` ingesting link churn.

Set-up hands the graph to a server (the certified cold state is solved
there) and applies the mix's warm-up batches, which compile the update's
device program. Each call of the window ingests the next batch of the
stream and applies it: one closed loop, one batch in flight. The snapshot
it publishes is kept.

After the window every published snapshot is compared with the float64
reference of the graph it claims, rebuilt from the stream's own link
sets: its L1 distance must be within the cell's limit `l1_err` (the
server's certified tolerance). A batch that left the
`sharded_push`/`device` path, or fell back to a full solve, fails.

Under the control (bench/control.py) no server is built: the stream runs
as in the benchmark, and each batch publishes the reference of the graph
it produced at the control's lower precision.
"""
from __future__ import annotations

import reference
from edges import Graph


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.alpha = float(ctx.config["alpha"])
        self.stream = ctx.traffic.make(ctx.mix, ctx.graph, ctx.seed)
        self.warmup = int(ctx.mix["warmup_batches"])
        self.published = []
        self.srv = self.dg = None
        if ctx.control is not None:
            for _ in range(self.warmup):
                next(self.stream)
            return
        from repro.streaming import DeltaGraph, RankServer
        from program import csr_graph

        args = ctx.workload["args"]
        self.dg = DeltaGraph(csr_graph(ctx.graph))
        self.srv = RankServer(
            self.dg, alpha=self.alpha, tol=float(args["tol"]),
            updater="sharded", shards=int(args["shards"]),
            shard_mode=args["shard_mode"],
            shard_transport=args["shard_transport"])
        ctx.phases.mark("cold_state")
        for _ in range(self.warmup):
            self.call(next(self.stream))

    def call(self, batch: dict):
        import jax
        from repro.streaming import EdgeDelta
        with jax.profiler.TraceAnnotation("bench.refresh"):
            self.srv.ingest(EdgeDelta(**batch))
            return self.srv.apply_pending()

    def step(self) -> dict:
        if self.ctx.control is not None:
            return self.control_step()
        fallbacks = self.srv.fallbacks
        st = self.call(next(self.stream))
        snap = self.srv.snapshot()
        ok = (st.path == "sharded_push" and st.transport == "device"
              and self.srv.fallbacks == fallbacks)
        self.published.append(dict(x=snap.x, version=snap.version,
                                   cert=float(snap.cert), on_path=ok))
        return dict(units=1, supersteps=int(st.supersteps),
                    attempts=int(st.attempts), path=st.path,
                    transport=st.transport, cert=float(st.cert))

    def control_step(self) -> dict:
        c = self.ctx.control
        next(self.stream)
        g = Graph.from_keys(self.ctx.graph.n, self.stream.keys)
        x = reference.pagerank_lowp(g, self.alpha, c["dtype"],
                                    tol=float(c["tol"]),
                                    max_iters=int(c["max_iters"]))
        self.published.append(dict(x=x, version=len(self.published),
                                   cert=float("nan"), on_path=True))
        return dict(units=1, supersteps=0, attempts=0, path="control",
                    transport="control", cert=float("nan"))

    def work(self) -> dict:
        return dict(nv=1, itemsize=8)

    def release(self) -> None:
        self.srv = self.dg = None

    def check(self):
        limit = float(self.ctx.workload["limits"]["l1_err"])
        ref_tol = float(self.ctx.workload["ref_tol"])
        replay = self.ctx.traffic.make(self.ctx.mix, self.ctx.graph,
                                       self.ctx.seed)
        for _ in range(self.warmup):
            next(replay)
        errs, off_path, x_ref = [], 0, None
        for snap in self.published:
            next(replay)
            g = Graph.from_keys(self.ctx.graph.n, replay.keys)
            x_ref = reference.pagerank(g, self.alpha, x0=x_ref, tol=ref_tol)
            errs.append(float(reference.l1(snap["x"], x_ref)))
            off_path += not snap["on_path"]
        failed = sum((e > limit) or not s["on_path"]
                     for e, s in zip(errs, self.published))
        return {"l1_err": {"value": max(errs, default=float("inf")),
                           "limit": limit},
                "off_path": {"value": off_path, "limit": 0}}, failed


def setup(ctx) -> Session:
    return Session(ctx)
