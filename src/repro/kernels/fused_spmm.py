"""Pallas TPU kernel: fused adaptive-edge-sampling + SpMM (Algorithm 1).

The closest structural match to the paper's kernel: sampling is performed
*inside* the SpMM kernel, and the sampled (val, col) pairs are staged in a
VMEM scratch tile — the direct analogue of ``__shared__ sh_val[], sh_col[]``.

Per row (Alg. 1 lines 3-14):
  W          = min(row_nnz, sh_width)
  (N, cnt)   = strategy table from R = row_nnz / W        (Table 1)
  start(i)   = (i * 1429) mod (row_nnz - N + 1)           (Eq. 3)
  slot i+j*cnt <- CSR element  row_start + start(i) + j   (strided layout)

then the SpMM stage (lines 16-19) accumulates over the staged slots.

TPU adaptation notes: each sample is one contiguous run of N elements, so
the staging uses **one DMA per sample** of a static size (the aligned
window over the run) and masks the tail — the paper's "coarser N = fewer
index computations" becomes "coarser N = fewer DMA descriptors" on TPU,
the same economy.  The sampled row lands in an SMEM scratch row (the
analogue of ``sh_val``/``sh_col``) with scalar stores, and the B-row
gather is the shared double-buffered core of ``gather.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampling import PRIME_NUM, _BANDS, _R_THRESHOLDS

from .gather import (TiledFeatures, flat_window, for_row_groups,
                     gather_accumulate, smem_words, stage_flat)


def smem_bytes(block_r: int, sh_width: int) -> int:
    """SMEM of :func:`fused_aes_spmm`: the sampled row (``sh_val``,
    ``sh_col``), the CSR run stages, and the double-buffered row-start and
    row-nnz blocks."""
    return 4 * (2 * smem_words((sh_width,))
                + 2 * smem_words((flat_window(sh_width),))
                + 2 * 2 * smem_words((1, block_r)))


def _strategy_scalar(row_nnz, sh_width: int):
    """Traced-scalar version of Table 1 (same math as core.sampling)."""
    W = jnp.minimum(row_nnz, sh_width)
    N = row_nnz
    cnt = jnp.int32(1)
    prev = row_nnz <= _R_THRESHOLDS[0] * W
    for t, (d, c) in zip(_R_THRESHOLDS[1:] + (None,), _BANDS):
        cond = (row_nnz <= t * W) if t is not None else True
        take = jnp.logical_and(jnp.logical_not(prev), cond)
        N = jnp.where(take, W // d, N)
        cnt = jnp.where(take, c, cnt)
        prev = jnp.logical_or(prev, cond)
    N = jnp.maximum(N, 1)
    cnt = jnp.minimum(cnt, jnp.maximum(W, 1))
    return W, N, cnt


def sample_row(ci_ref, av_ref, stage_i, stage_f, sem, row_start, row_nnz,
               sh_width: int, put):
    """Alg. 1 lines 7-14 for one row: each of the ``cnt`` samples copies
    its CSR run into the SMEM stages and hands its N elements to
    ``put(slot, col, val)`` for slots ``i + j * cnt``.

    Returns the row's live width: the slots ``[0, live)`` are all put.
    """
    W, N, cnt = _strategy_scalar(row_nnz, sh_width)
    span = jnp.maximum(row_nnz - N + 1, 1)

    def sample_body(i, carry):
        start = (i * PRIME_NUM) % span
        off = stage_flat(((ci_ref, stage_i), (av_ref, stage_f)), sem,
                         row_start + start, sh_width)

        def elem_body(j, c):
            put(i + j * cnt, stage_i[off + j], stage_f[off + j])
            return c

        jax.lax.fori_loop(0, jnp.minimum(N, sh_width), elem_body, 0)
        return carry

    live = row_nnz > 0
    jax.lax.fori_loop(0, jnp.where(live, cnt, 0), sample_body, 0)
    return jnp.where(live, jnp.minimum(N * cnt, W), 0)


def _fused_kernel(rs_ref, nnz_ref, ci_ref, av_ref, b_ref, out_ref,
                  sh_val, sh_col, stage_i, stage_f, bsc, sem, bsem,
                  *, sh_width: int):
    """grid = (row_tiles, feat_tiles).

    rs_ref/nnz_ref: i32[1, block_r] SMEM — CSR row starts / row nnz
    ci_ref/av_ref:  HBM — full CSR col_ind / val arrays (padded)
    b_ref:          HBM — dense features [feat tiles, nodes, block_f]
    sh_val/sh_col:  SMEM scratch [sh_width] — the "shared memory" of one row
    stage_i/stage_f: SMEM scratch — aligned landing windows of a CSR run
    bsc:            VMEM scratch [2, 1, 1, block_f] — B-row landing zone
    """
    b_tile = b_ref.at[pl.ds(pl.program_id(1), 1)]

    def row_acc(base, i):
        r = base + i

        def put(slot, c, v):
            sh_col[slot] = c
            sh_val[slot] = v

        # --- sampling stage: fill sh_val/sh_col (Alg. 1 lines 7-14) -------
        live = sample_row(ci_ref, av_ref, stage_i, stage_f, sem,
                          rs_ref[0, r], nnz_ref[0, r], sh_width, put)
        # --- SpMM stage over staged slots (Alg. 1 lines 16-19) ------------
        return gather_accumulate(b_tile, bsc, bsem, live,
                                 lambda k: sh_col[k], lambda k: sh_val[k])

    def store(base, tile):
        out_ref[pl.ds(base, tile.shape[0]), :] = tile

    for_row_groups(out_ref.shape[0], row_acc, store)


@functools.partial(
    jax.jit, static_argnames=("sh_width", "block_r", "interpret"))
def fused_aes_spmm(row_start, row_nnz, col_ind, val, b: TiledFeatures, *,
                   sh_width: int, block_r: int = 8, interpret: bool = True):
    """AES-SpMM with sampling fused into the kernel (paper Alg. 1).

    ``col_ind``/``val`` must be padded by >= ``flat_window(sh_width)``
    trailing elements so the fixed-size sample DMA never reads out of
    bounds (ops.py pads).  ``b`` is a float operand.

    Returns f32[rows, b.padded_features].
    """
    rows = row_start.shape[0]
    block_f = b.width
    feat = b.padded_features
    assert rows % block_r == 0 and b.bits is None

    grid = (rows // block_r, feat // block_f)
    kernel = functools.partial(_fused_kernel, sh_width=sh_width)
    stage = flat_window(sh_width)
    return pl.pallas_call(
        kernel,
        name="fused_aes_spmm",
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, block_r), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, block_r), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_r, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, feat), jnp.float32),
        scratch_shapes=[
            pltpu.SMEM((sh_width,), jnp.float32),           # sh_val
            pltpu.SMEM((sh_width,), jnp.int32),             # sh_col
            pltpu.SMEM((stage,), jnp.int32),                # CSR col run stage
            pltpu.SMEM((stage,), jnp.float32),              # CSR val run stage
            pltpu.VMEM((2, 1, 1, block_f), b.tiles.dtype),     # B-row stage
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(row_start.astype(jnp.int32).reshape(rows // block_r, 1, block_r),
      row_nnz.astype(jnp.int32).reshape(rows // block_r, 1, block_r),
      col_ind, val, b.tiles)
