"""Hands the benchmark's graph to the program in the program's own form."""
import numpy as np


def csr_graph(graph):
    from repro.graph.csr import CSRGraph
    return CSRGraph(n=graph.n, indptr=graph.indptr(),
                    indices=graph.dst.astype(np.int32))
