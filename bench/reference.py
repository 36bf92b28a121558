"""Plain reference for the benchmark's correctness check.

Written from the paper and the program's documented semantics, in numpy
and scipy on the host.  It imports nothing of the program and takes
nothing the program made: it samples the graph itself, quantizes the
features itself and aggregates in float64.

* :func:`aes_sample` is the AES selection of paper Alg. 1: the strategy
  table (Table 1) on ``R = nnz / W``, the hash start (Eq. 3, prime 1429)
  and the strided slot layout (slot ``i + j * cnt`` holds element ``j`` of
  sample ``i``).
* :func:`quantize` is Eq. 1 with one global range, rounded half up, and
  Eq. 2 for the reconstruction, both evaluated in float32 as the program
  documents them.
* :func:`requant_guard` is the program's documented guard for an operand
  fed to a quantized aggregation: re-encode it with the stored range;
  serve it as float if it leaves that range by more than half a step;
  derive a fresh range if the range moved by more than a quarter of the
  stored span.
* :class:`Arith` does the products: float64 (``"highest"``) or the
  three-pass bfloat16 split of a TPU's ``high`` precision (``"high"``),
  which the control uses.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import scipy.sparse as sp

PRIME = 1429
# Table 1: rows with nnz <= t * W fall in a band; above R = 1 the band sets
# N = W // d consecutive elements for each of c samples.
BANDS = ((2, 4, 4), (36, 8, 8), (54, 16, 16), (None, 32, 32))

DRIFT = 0.25          # share of the stored span the range may move


def aes_sample(row_ptr, col_ind, val, sh_width: int):
    """AES-sampled ELL ``(val f32[n, W], col i32[n, W])``, dead slots 0."""
    row_ptr = np.asarray(row_ptr, np.int64)
    nnz = np.diff(row_ptr)
    n, w = nnz.shape[0], sh_width
    width = np.minimum(nnz, w)
    big = nnz > w
    N = nnz.copy()
    cnt = np.ones_like(nnz)
    done = ~big
    for t, d, c in BANDS:
        take = ~done if t is None else (~done & (nnz <= t * width))
        N[take], cnt[take] = width[take] // d, c
        done |= take
    N, cnt = np.maximum(N, 1), np.minimum(cnt, np.maximum(width, 1))
    s = np.arange(w)[None, :]
    i, j = s % cnt[:, None], s // cnt[:, None]
    span = np.maximum(nnz - N + 1, 1)[:, None]
    off = (i * PRIME) % span + j
    live = (s < (N * cnt)[:, None]) & (off < nnz[:, None]) \
        & (nnz[:, None] > 0)
    idx = np.where(live, row_ptr[:-1, None] + off, 0)
    ell_col = np.where(live, np.asarray(col_ind)[idx], 0).astype(np.int32)
    ell_val = np.where(live, np.asarray(val)[idx], 0).astype(np.float32)
    return ell_val, ell_col


# -- quantization (paper Eq. 1-2, as the program documents them) -----------

class Quantized(NamedTuple):
    levels: np.ndarray      # float32 level index of each element
    x_min: np.float32
    x_max: np.float32
    bits: int

    @property
    def scale(self) -> np.float32:
        return np.float32((self.x_max - self.x_min)
                          / np.float32(2 ** self.bits - 1))

    def dequantize(self) -> np.ndarray:
        return self.levels * self.scale + self.x_min


def quantize(x, bits: int, x_min=None, x_max=None) -> Quantized:
    """Eq. 1 in float32 over one global range (``x``'s own by default)."""
    x = np.asarray(x, np.float32)
    x_min = np.float32(x.min() if x_min is None else x_min)
    x_max = np.float32(x.max() if x_max is None else x_max)
    levels = np.float32(2 ** bits - 1)
    span = np.maximum(x_max - x_min, np.finfo(np.float32).tiny)
    q = np.floor((x - x_min) / span * levels + np.float32(0.5))
    return Quantized(np.clip(q, 0, levels).astype(np.float32), x_min, x_max,
                     bits)


def requant_guard(stored: Quantized, x):
    """What a quantized aggregation serves for operand ``x``: its
    reconstruction under the stored range, under a fresh range, or ``x``
    itself (float) when it left the stored range."""
    x = np.asarray(x, np.float32)
    half = stored.scale * np.float32(0.5)
    if x.min() < stored.x_min - half or x.max() > stored.x_max + half:
        return x
    span = max(float(stored.x_max - stored.x_min),
               float(np.finfo(np.float32).tiny))
    drift = max(abs(float(x.min()) - float(stored.x_min)),
                abs(float(x.max()) - float(stored.x_max))) / span
    if drift > DRIFT:
        return quantize(x, stored.bits).dequantize()
    return quantize(x, stored.bits, stored.x_min, stored.x_max).dequantize()


# -- arithmetic ---------------------------------------------------------------

def bf16_split(x):
    """``x`` (float32) as ``hi + lo``, each rounded to bfloat16 (nearest,
    ties to even); what a three-pass bfloat16 product sees of ``x``."""
    def rnd(a):
        u = np.ascontiguousarray(a, np.float32).view(np.uint32)
        u = (u + np.uint32(0x7FFF) + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
        return u.view(np.float32)

    x = np.asarray(x, np.float32)
    hi = rnd(x)
    return hi, rnd(x - hi)


class Arith:
    """Products at one precision: ``"highest"`` multiplies the float32
    operands in float64; ``"high"`` keeps ``a_hi b_hi + a_hi b_lo +
    a_lo b_hi`` of their bfloat16 splits, the three passes of a TPU's
    ``high`` matmul precision."""

    def __init__(self, precision: str = "highest"):
        if precision not in ("highest", "high"):
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision

    def _terms(self, a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if self.precision == "highest":
            return [(a, b)]
        (ah, al), (bh, bl) = bf16_split(a), bf16_split(b)
        return [(ah, bh), (ah, bl), (al, bh)]

    def matmul(self, a, b) -> np.ndarray:
        return sum(np.asarray(x, np.float64) @ np.asarray(y, np.float64)
                   for x, y in self._terms(a, b))

    def aggregate(self, ell_val, ell_col, x) -> np.ndarray:
        """``out[r] = sum_s val[r, s] * x[col[r, s]]`` over every slot,
        duplicates included (dead slots carry value 0)."""
        n, w = ell_val.shape
        indptr = np.arange(0, n * w + 1, w)
        out = 0.0
        for v, xs in self._terms(ell_val, x):
            a = sp.csr_matrix((np.asarray(v, np.float64).ravel(),
                               ell_col.ravel(), indptr),
                              shape=(n, x.shape[0]))
            out = out + a @ np.asarray(xs, np.float64)
        return np.asarray(out)


def relu(x):
    return np.maximum(x, 0.0)
