"""2-layer GraphSAGE with the mean aggregator: the program's forward and
its plain reference.

    h      = relu(X Ws1 + (A' X) Wn1 + b1)
    logits = h Ws2 + (A' h) Wn2 + b2,          A' = D^-1 A

``A'`` is AES-sampled at width W before each aggregation.
"""
from __future__ import annotations

from bench.reference import relu

ADJACENCY = "mean"
SHAPES = {"w_self1": ("F", "H"), "w_neigh1": ("F", "H"), "b1": ("H",),
          "w_self2": ("H", "C"), "w_neigh2": ("H", "C"), "b2": ("C",)}


def program(params, adj, x, agg):
    """The program's GraphSAGE over aggregation ``agg``."""
    from repro.gnn.models import GraphSAGE, SAGEParams

    return GraphSAGE(SAGEParams(**params), adj, x, agg)


def reference(p, x, aggregate, ar) -> dict:
    """The forward in float64 with ``ar``'s products; ``aggregate(h,
    layer)`` is the sampled aggregation of layer 0 or 1."""
    a1 = aggregate(x, 0)
    h = relu(ar.matmul(x, p["w_self1"]) + ar.matmul(a1, p["w_neigh1"])
             + p["b1"])
    a2 = aggregate(h, 1)
    logits = ar.matmul(h, p["w_self2"]) + ar.matmul(a2, p["w_neigh2"]) \
        + p["b2"]
    return {"agg1": a1, "hidden": h, "agg2": a2, "logits": logits}


def flops(n: int, f: int, h: int, c: int, edges: int) -> int:
    """Two sampled aggregations and four dense transforms."""
    return 2 * edges * (f + h) + 4 * n * (f * h + h * c)
