"""2-layer GCN (Kipf & Welling): the program's forward and its plain
reference.

    logits = A' relu(A' X W1 + b1) W2 + b2,   A' = D^-1/2 (A + I) D^-1/2

``A'`` is AES-sampled at width W before each aggregation.
"""
from __future__ import annotations

from bench.reference import relu

ADJACENCY = "gcn"
# name -> shape, in terms of the feature width F, hidden H and classes C
SHAPES = {"w1": ("F", "H"), "b1": ("H",), "w2": ("H", "C"), "b2": ("C",)}


def program(params, adj, x, agg):
    """The program's GCN over aggregation ``agg``."""
    from repro.gnn.models import GCN, GCNParams

    return GCN(GCNParams(**params), adj, x, agg)


def reference(p, x, aggregate, ar) -> dict:
    """The forward in float64 with ``ar``'s products; ``aggregate(h,
    layer)`` is the sampled aggregation of layer 0 or 1."""
    a1 = aggregate(x, 0)
    h = relu(ar.matmul(a1, p["w1"]) + p["b1"])
    a2 = aggregate(h, 1)
    return {"agg1": a1, "hidden": h, "agg2": a2,
            "logits": ar.matmul(a2, p["w2"]) + p["b2"]}


def flops(n: int, f: int, h: int, c: int, edges: int) -> int:
    """Two sampled aggregations and two dense transforms."""
    return 2 * edges * (f + h) + 2 * n * (f * h + h * c)
