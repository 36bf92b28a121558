"""Pallas TPU kernels: SpMM over the AES-sampled ELL layout, plus the
block-dispatched variant over the mixed-width BlockELL layout.

This is the SpMM stage of Algorithm 1 (lines 16-19), re-thought for TPU:

  * the sampled ``(val, col)`` tiles are staged in **SMEM** by ``BlockSpec``
    — the analogue of the paper's shared-memory staging, and the memory a
    DMA address must come from;
  * the dense feature matrix B stays in **HBM** (``pl.ANY``), feature-tile
    major; each referenced row is DMA'd into a VMEM landing zone, double
    buffered so the copy of row k+1 overlaps the FMA of row k (the shared
    core in ``gather.py``);
  * one Pallas program per (row-tile x feature-tile) replaces one CUDA
    thread per output element; the per-row ``k in [0, live_w)`` loop is the
    paper's ``for k <- 0 to W`` with the same dynamic bound
    ``W = min(row_nnz, sh_width)``;
  * where a row spans several lane tiles, one program per row tile
    instead fetches all of a row's tiles in one DMA and keeps
    ``RING_DEPTH`` such DMAs in flight, so each edge list is walked once.

A quantized B (a ``TiledFeatures`` with ``bits`` set, on both the
fixed-width and the block-dispatched kernel) stays packed in HBM and Eq. 2
dequantization is fused into the gather — beyond-paper: from F > 128 it
cuts the gather's HBM bytes up to 4x (a row DMA moves at least 128 words,
so at F = 128 a uint8 row costs as much as a float one).  The blocked
kernel is additionally launched once per *width bucket* by the ops wrapper,
so narrow tail blocks stage their rows with a narrow static DMA instead of
the global max width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (FLAT_TILE, TiledFeatures, flat_window,
                                  for_row_groups, gather_accumulate,
                                  gather_accumulate_ring, row_group,
                                  smem_words, stage_flat, stage_rows,
                                  vmem_limit)


def block_smem_bytes(block_rows: int, max_w: int) -> int:
    """SMEM of :func:`block_ell_spmm` for one width bucket: the val/col
    staging windows, and the double-buffered block-table and live-width
    blocks."""
    stage = flat_window(stage_rows(row_group(block_rows), max_w) * max_w)
    return 4 * (2 * smem_words((stage,))
                + 2 * (smem_words((1, 2)) + smem_words((1, block_rows))))


# Row DMAs the multi-tile gather keeps in flight.  scripts/gather_probe.py
# on a v5e, ns per tile-row at 5 lane tiles on Reddit's shapes: 18.07 at
# 4, 9.67 at 8, 7.76 at 16, 7.78 at 32 (two-slot loop 159.22); over every
# configuration at 2-5 tiles 16 came within 5.6% of the best depth on
# average, 8 within 12.6%, 32 within 15.0%.
RING_DEPTH = 16


def ring_gather(b: TiledFeatures) -> bool:
    """Whether :func:`ell_spmm` gathers ``b`` with the ring: only where a
    row spans two or more lane tiles.  A one-tile row keeps the two-slot
    loop, which on a one-tile operand was faster than the ring."""
    return b.tiles.shape[0] > 1


def _ell_spmm_kernel(val_ref, col_ref, live_ref, b_ref, out_ref, bsc, sem,
                     *, ring: bool, pack: int, bits, scale: float,
                     x_min: float):
    """grid = (row_tiles, feat_tiles), or (row_tiles,) with ``ring``.

    val_ref:  f32[block_r, W]   SMEM   sampled edge weights
    col_ref:  i32[block_r, W]   SMEM   sampled column indices
    live_ref: i32[1, block_r]   SMEM   live width per row (= min(nnz, W))
    b_ref:    [tiles, num_nodes, block_f] HBM  dense features, f32 or
              quantized ones packed ``pack`` to a word (``TiledFeatures``)
    out_ref:  f32[block_r, pack * block_f] VMEM, one feature tile; with
              ``ring`` f32[block_r, tiles * pack * block_f], the whole row
    bsc:      [2, 1, 1, block_f] VMEM   double-buffered B-row landing zone;
              with ``ring`` [RING_DEPTH, tiles, 1, block_f], every tile of
              a row landing from one DMA
    sem:      DMA semaphores [2], with ``ring`` [RING_DEPTH]
    """
    if ring:
        b_tile, gather = b_ref, gather_accumulate_ring
    else:
        b_tile, gather = b_ref.at[pl.ds(pl.program_id(1), 1)], \
            gather_accumulate

    def row_acc(base, i):
        r = base + i
        return gather(
            b_tile, bsc, sem, live_ref[0, r], lambda k: col_ref[r, k],
            lambda k: val_ref[r, k], pack=pack, bits=bits, scale=scale,
            x_min=x_min)

    def store(base, tile):
        out_ref[pl.ds(base, tile.shape[0]), :] = tile

    for_row_groups(out_ref.shape[0], row_acc, store)


@functools.partial(
    jax.jit, static_argnames=("block_r", "interpret", "scale", "x_min"))
def ell_spmm(ell_val, ell_col, live_w, b: TiledFeatures, *,
             block_r: int = 8, scale=1.0, x_min=0.0, interpret: bool = True):
    """C[r, :] = sum_k ell_val[r, k] * B[ell_col[r, k], :].

    Rows must be padded to a multiple of ``block_r`` (``repro.kernels.ops``
    pads).  A quantized B (``b.bits`` set) is dequantized in the gather
    with Eq. 2, ``q * scale + x_min``, and covers ``pack * width``
    features per program.

    A one-tile B runs one program per (row tile x feature tile) with two
    row DMAs in flight.  Where a row spans several lane tiles
    (:func:`ring_gather`), one program per row tile fetches every tile of
    a sampled row in one DMA, ``RING_DEPTH`` of them in flight, and walks
    each edge list once.  Both sum in the same order.

    Returns f32[rows, b.padded_features].
    """
    return _launch(ell_val, ell_col, live_w, b, ring=ring_gather(b),
                   block_r=block_r, scale=scale, x_min=x_min,
                   interpret=interpret)


def _launch(ell_val, ell_col, live_w, b: TiledFeatures, *, ring: bool,
            block_r: int, scale, x_min, interpret: bool):
    """The ``ell_spmm`` Pallas call, on the ring or on the per-tile grid."""
    rows, width = ell_val.shape
    assert rows % block_r == 0
    block_f, pack = b.width, b.pack
    feat = b.padded_features
    bt = b.tiles

    if ring:
        grid, tile, zone = (rows // block_r,), feat, (
            RING_DEPTH, bt.shape[0], 1, block_f)
    else:
        tile = pack * block_f
        grid, zone = (rows // block_r, feat // tile), (2, 1, 1, block_f)
    kernel = functools.partial(
        _ell_spmm_kernel, ring=ring, pack=pack, scale=scale, x_min=x_min,
        bits=b.bits)
    return pl.pallas_call(
        kernel,
        name="ell_spmm",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, width), lambda i, j=0: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_r, width), lambda i, j=0: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, block_r), lambda i, j=0: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_r, tile), lambda i, j=0: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, feat), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM(zone, bt.dtype),
            pltpu.SemaphoreType.DMA((zone[0],)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)),
    )(ell_val, ell_col,
      live_w.astype(jnp.int32).reshape(rows // block_r, 1, block_r), bt)


# ---------------------------------------------------------------------------
# Block-dispatched SpMM over the mixed-width BlockELL layout.
# ---------------------------------------------------------------------------

def _block_ell_spmm_kernel(table_ref, live_ref, val_ref, col_ref, b_ref,
                           out_ref, stage_v, stage_c, bsc, ssem, bsem,
                           *, max_w: int, pack: int, bits, scale: float,
                           x_min: float):
    """grid = (num_blocks, feat_tiles) — one program per (row block x F tile).

    table_ref: i32[1, 2]          SMEM  this block's (slot offset, width)
    live_ref:  i32[1, block_rows] SMEM  live slots per row
    val_ref:   f32[slots + tail]  HBM   flattened mixed-width segments
    col_ref:   i32[slots + tail]  HBM
    b_ref:     [tiles, num_nodes, block_f] HBM  dense features, f32 or
        quantized ones packed ``pack`` to a word (Eq. 2 fuses into the
        gather)
    out_ref:   f32[block_rows, pack * block_f] VMEM
    stage_v/stage_c: SMEM  slot landing zones: the aligned window over
        the ``max_w`` slots of each row of a row group (of one row, when
        the group's window would crowd SMEM), one DMA at a static size;
        the live_w bound masks the tail
    bsc:       VMEM[2, 1, 1, block_f] double-buffered B-row landing zone

    Each program reads its own width from the block table.  The economy of
    a narrow tail block is in its accumulation loop (live_w-bounded) and
    its HBM footprint (narrow flat segments); the staging DMA is sized by
    ``max_w`` — Pallas copy sizes are static, so the ops wrapper groups
    blocks into *width buckets* and issues one launch per bucket with
    ``max_w`` = that bucket's widest block, keeping narrow blocks off
    max-width DMAs.
    """
    b_tile = b_ref.at[pl.ds(pl.program_id(1), 1)]
    seg_off = table_ref[0, 0]
    width = table_ref[0, 1]
    per = stage_rows(row_group(out_ref.shape[0]), max_w)

    def row_acc(base, i):
        j = i % per                         # row within the staged rows
        start = seg_off + (base + i - j) * width
        if j == 0:
            stage_flat(((val_ref, stage_v), (col_ref, stage_c)), ssem,
                       start, per * max_w)
        off = start % FLAT_TILE + j * width
        return gather_accumulate(
            b_tile, bsc, bsem, live_ref[0, base + i],
            lambda k: stage_c[off + k], lambda k: stage_v[off + k],
            pack=pack, bits=bits, scale=scale, x_min=x_min)

    def store(base, tile):
        out_ref[pl.ds(base, tile.shape[0]), :] = tile

    for_row_groups(out_ref.shape[0], row_acc, store)


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "max_w", "scale", "x_min", "interpret"))
def block_ell_spmm(table, live_w, val_flat, col_flat, b: TiledFeatures, *,
                   block_rows: int, max_w: int, scale=1.0, x_min=0.0,
                   interpret: bool = True):
    """C[r, :] = sum_k seg_val[r, k] * B[seg_col[r, k], :] over mixed-width
    block segments.

    Args:
      table: i32[num_blocks, 2] — per-block (flat slot offset, ELL width).
        With width bucketing the ops wrapper passes only one bucket's
        blocks here; the launch is then ``max_w``-wide for exactly those.
      live_w: i32[num_blocks * block_rows] live slots per row.
      val_flat / col_flat: flattened segments, padded by
        ``block_tail(max_w)`` trailing elements so the fixed-size staging
        DMA never over-reads (``repro.kernels.ops.block_ell_spmm`` pads).
      b: the dense operand, f32 or quantized (``b.bits`` set).
      max_w: max width over the blocks in ``table`` — static row-DMA size.
      scale / x_min: Eq. 2 (``q * scale + x_min``), fused into the B-row
        gather of a quantized B.

    Returns f32[num_blocks * block_rows, b.padded_features].
    """
    num_blocks = table.shape[0]
    rows = num_blocks * block_rows
    block_f, pack = b.width, b.pack
    tile = pack * block_f
    feat = b.padded_features
    bt = b.tiles

    grid = (num_blocks, feat // tile)
    kernel = functools.partial(
        _block_ell_spmm_kernel, max_w=max_w, pack=pack, scale=scale,
        x_min=x_min, bits=b.bits)
    stage = flat_window(stage_rows(row_group(block_rows), max_w) * max_w)
    return pl.pallas_call(
        kernel,
        name="block_ell_spmm",
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, 2), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, block_rows), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_rows, tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, feat), jnp.float32),
        scratch_shapes=[
            pltpu.SMEM((stage,), jnp.float32),      # group val landing zone
            pltpu.SMEM((stage,), jnp.int32),        # group col landing zone
            pltpu.VMEM((2, 1, 1, block_f), bt.dtype),  # B-row landing zone
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the double-buffered output block dominates: 4096 rows of a
            # 512-feature quantized tile alone fill the 16 MiB default
            vmem_limit_bytes=vmem_limit(2 * block_rows * tile * 4)),
    )(table.reshape(num_blocks, 1, 2),
      live_w.astype(jnp.int32).reshape(num_blocks, 1, block_rows),
      val_flat, col_flat, bt)
