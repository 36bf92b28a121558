"""GCN (Kipf & Welling) and GraphSAGE (mean aggregator) — the paper's two
evaluation models (§4.1), with the aggregation step pluggable so inference
can swap cuSPARSE-role / GE-SpMM-role / ES-SpMM / AES-SpMM kernels.

Aggregation signature: ``agg(csr, h) -> h'`` — exactly the SpMM
``F = A @ H`` of paper §2.1.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import CSR
from repro.kernels import ref

AggFn = Callable[[CSR, jax.Array], jax.Array]


def exact_agg(csr: CSR, h: jax.Array) -> jax.Array:
    """cuSPARSE-role aggregation (no sampling, exact)."""
    return ref.csr_spmm(csr.row_ptr, csr.col_ind, csr.val, h)


def make_sampled_agg(sh_width: int, strategy: str = "aes",
                     backend: str = "jax", quantized=None) -> AggFn:
    from repro.core.aes_spmm import aes_spmm

    def agg(csr: CSR, h: jax.Array) -> jax.Array:
        return aes_spmm(csr, h, sh_width, strategy=strategy, backend=backend,
                        quantized=quantized)

    return agg


def make_presampled_agg(csr: CSR, sh_width: int, strategy: str = "aes",
                        backend: str = "jax") -> AggFn:
    """Beyond-paper: sample once, reuse the ELL across layers/calls
    (the paper's kernel resamples on every SpMM)."""
    from repro.core.aes_spmm import sample

    ell = sample(csr, sh_width, strategy)

    def agg(_csr: CSR, h: jax.Array) -> jax.Array:
        if backend == "pallas":
            from repro.kernels import ops

            return ops.ell_spmm(ell, h)
        return ref.ell_spmm_rowloop(ell.val, ell.col, h)

    return agg


class GCNParams(NamedTuple):
    w1: jax.Array
    b1: jax.Array
    w2: jax.Array
    b2: jax.Array


def init_gcn(rng: np.random.Generator, feat: int, hidden: int,
             classes: int) -> GCNParams:
    g = lambda *s: jnp.asarray(
        rng.normal(size=s).astype(np.float32) / np.sqrt(s[0]))
    return GCNParams(g(feat, hidden), jnp.zeros(hidden),
                     g(hidden, classes), jnp.zeros(classes))


def GCN(params: GCNParams, adj: CSR, x: jax.Array,
        agg: AggFn = exact_agg) -> jax.Array:
    """2-layer GCN: softmax(A' relu(A' X W1) W2) with A' pre-normalized."""
    a = agg(adj, x)
    with obs.trace("gnn.dense", layer=1):
        h = jax.nn.relu(a @ params.w1 + params.b1)
    a = agg(adj, h)
    with obs.trace("gnn.dense", layer=2):
        return a @ params.w2 + params.b2


class SAGEParams(NamedTuple):
    w_self1: jax.Array
    w_neigh1: jax.Array
    b1: jax.Array
    w_self2: jax.Array
    w_neigh2: jax.Array
    b2: jax.Array


def init_sage(rng: np.random.Generator, feat: int, hidden: int,
              classes: int) -> SAGEParams:
    g = lambda *s: jnp.asarray(
        rng.normal(size=s).astype(np.float32) / np.sqrt(s[0]))
    return SAGEParams(g(feat, hidden), g(feat, hidden), jnp.zeros(hidden),
                      g(hidden, classes), g(hidden, classes), jnp.zeros(classes))


def GraphSAGE(params: SAGEParams, adj: CSR, x: jax.Array,
              agg: AggFn = exact_agg) -> jax.Array:
    """2-layer GraphSAGE-mean: h' = relu(W_self h + W_neigh mean_agg(h))."""
    a = agg(adj, x)
    with obs.trace("gnn.dense", layer=1):
        h = jax.nn.relu(x @ params.w_self1 + a @ params.w_neigh1
                        + params.b1)
    a = agg(adj, h)
    with obs.trace("gnn.dense", layer=2):
        return h @ params.w_self2 + a @ params.w_neigh2 + params.b2


MODELS = {
    "gcn": (init_gcn, GCN, "gcn_adj"),
    "graphsage": (init_sage, GraphSAGE, "sage_adj"),
}
