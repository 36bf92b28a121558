"""The benchmark's graphs, its work counts and its table of peaks.

Graphs are a seeded stochastic block model matched to a configuration's
``graph`` group (nodes, average degree, degree skew, classes, feature
width, homophily).  It is the benchmark's own copy of the generator, so a
change to the program's dataset code does not move the yardstick.

Two seeds make a graph:

* the configuration's ``degree_seed`` fixes the degree sequence, so every
  run seed does the same amount of work (same rows, same row lengths, same
  sampled-edge count);
* the run's ``--seed`` draws the edge endpoints, the features and the
  weights.

Work counts are taken from shapes: the FLOPs of a forward and a lower
bound on the bytes it must move, from which a least time and a share of
the chip's peak follow.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(197 TFLOP/s bf16, 819 GB/s HBM)",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in :data:`PEAKS` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to PEAKS with its source") from None


@dataclass(frozen=True)
class Graph:
    """A normalized adjacency in CSR form, on the host.

    ``row_ptr`` int32[n + 1], ``col_ind`` int32[nnz], ``val`` f32[nnz].
    """

    row_ptr: np.ndarray
    col_ind: np.ndarray
    val: np.ndarray

    @property
    def num_nodes(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def row_nnz(self) -> np.ndarray:
        return np.diff(self.row_ptr)


def degree_sequence(g: dict) -> np.ndarray:
    """In-degrees of the configuration's graph (before self loops), from
    its ``degree_seed``: a Pareto tail of shape ``skew`` for a skewed
    graph, Poisson else; capped at ``max_degree`` (``n - 1`` where the
    group gives none).  The degrees sum to ``round(nodes * avg_degree)``
    exactly: the shape is scaled (by bisection) until its rounded-down
    degrees, each at least 1, fall just short of that sum, and the rows
    with the largest fractional parts take the remaining edges."""
    rng = np.random.default_rng(g["degree_seed"])
    n, avg = g["nodes"], g["avg_degree"]
    cap = min(g.get("max_degree", n - 1), n - 1)
    total = int(round(n * avg))
    if g["skew"] > 0:
        shape = rng.pareto(g["skew"], n) + 0.25
        shape *= avg / shape.mean()
    else:
        shape = rng.poisson(avg, n).astype(np.float64)

    def floored(scale):
        t = np.minimum(shape * scale, cap)
        return t, np.maximum(np.floor(t).astype(np.int64), 1)

    lo, hi = 0.0, 2.0
    while floored(hi)[1].sum() <= total and hi < 2.0 ** 20:
        hi *= 2
    for _ in range(64):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if floored(mid)[1].sum() <= total else (lo, mid)
    target, deg = floored(lo)
    short = total - int(deg.sum())
    rows = np.flatnonzero(deg < cap)
    rows = rows[np.argsort(-(target - np.floor(target))[rows],
                           kind="stable")][:short]
    if short < 0 or rows.shape[0] != short:
        raise ValueError(f"cannot reach {total} edges under cap {cap}")
    deg[rows] += 1
    return deg


def communities(n: int, classes: int) -> np.ndarray:
    """Contiguous class blocks: node ``i`` is in class ``i * C // n``."""
    return (np.arange(n, dtype=np.int64) * classes) // n


def make_graph(g: dict, seed: int, normalize: str) -> Graph:
    """The normalized adjacency of graph group ``g`` for run ``seed``.

    Each node ``d`` receives ``deg[d]`` edges; an edge's source lies in
    ``d``'s class with probability ``homophily`` and is uniform otherwise.
    ``normalize`` is ``"gcn"`` (self loops, then D^-1/2 (A+I) D^-1/2) or
    ``"mean"`` (D^-1 A).  Duplicate edges are kept, as the CSR of a
    multigraph.
    """
    rng = np.random.default_rng(seed)
    n, classes = g["nodes"], g["classes"]
    deg = degree_sequence(g)
    comm = communities(n, classes)
    bounds = np.searchsorted(comm, np.arange(classes + 1))
    dst = np.repeat(np.arange(n, dtype=np.int64), deg)
    m = dst.shape[0]
    lo, size = bounds[:-1][comm[dst]], np.diff(bounds)[comm[dst]]
    same = lo + (rng.random(m) * size).astype(np.int64)
    src = np.where(rng.random(m) < g["homophily"], same,
                   rng.integers(0, n, m))
    if normalize == "gcn":
        src = np.concatenate([src, np.arange(n)])
        dst = np.concatenate([dst, np.arange(n)])
    order = np.lexsort((src, dst))
    src, dst = src[order], dst[order]
    counts = np.bincount(dst, minlength=n)
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    deg_row = counts.astype(np.float64)
    if normalize == "gcn":
        deg_col = np.bincount(src, minlength=n).astype(np.float64)
        val = 1.0 / np.sqrt(np.maximum(deg_row, 1.0))[dst] \
            / np.sqrt(np.maximum(deg_col, 1.0))[src]
    elif normalize == "mean":
        val = 1.0 / np.maximum(deg_row, 1.0)[dst]
    else:
        raise ValueError(f"unknown normalization {normalize!r}")
    return Graph(row_ptr.astype(np.int32), src.astype(np.int32),
                 val.astype(np.float32))


def sampled_edges(row_nnz: np.ndarray, sh_width: int) -> int:
    """Live slots of the AES-sampled ELL operand: ``min(nnz, W)`` a row
    (every band of the strategy table fills all ``W`` slots of a long
    row)."""
    return int(np.minimum(row_nnz, sh_width).sum())


# -- work counts ------------------------------------------------------------

def spmm_bytes(nodes: int, feat: int, edges: int, itemsize: int) -> int:
    """Least bytes of one sampled SpMM ``C = A_s @ B``: B read once
    (``itemsize`` bytes an element), the sampled operand's f32 value and
    int32 column once per live slot, C written once in f32."""
    return nodes * feat * itemsize + 8 * edges + 4 * nodes * feat


def spmm_flops(feat: int, edges: int) -> int:
    """One multiply and one add per live slot and feature."""
    return 2 * edges * feat


def forward_bytes(nodes: int, feat: int, classes: int, edges: int,
                  weights: int, itemsize: int) -> int:
    """Least bytes of a whole forward: the input features read once at
    ``itemsize`` bytes an element, the f32 weights (``weights`` elements)
    and the sampled operand once, the f32 logits written once.  Nothing
    in between is counted, and no CSR: the sampled operand depends only
    on the graph."""
    return nodes * feat * itemsize + 4 * weights + 8 * edges \
        + 4 * nodes * classes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: the larger of the compute and the
    memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def share(least_s: float, measured_s: float) -> float | None:
    """``least_s`` over ``measured_s`` in percent; ``None`` when nothing
    was measured."""
    if not measured_s or measured_s <= 0:
        return None
    return 100.0 * least_s / measured_s
