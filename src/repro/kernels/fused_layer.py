"""Pallas TPU kernel: one whole GNN layer in a single launch.

A GCN layer is ``relu(agg(A, X) @ W + b)`` — run as separate XLA ops the
aggregation output ``agg(A, X)`` round-trips HBM between the SpMM and the
dense transform, and a quantized deployment additionally pays a
dequantize pass at the feature boundary.  This kernel fuses the whole
layer per row tile:

  * the sampled ``(val, col)`` tile and the per-row live widths stage in
    SMEM via ``BlockSpec`` (same layout as ``ell_spmm.py``);
  * each referenced B row is DMA'd from HBM with double buffering,
    dequantized in the gather when the operand is int8 (the shared core
    of ``gather.py``, with the same Eq. 2 epilogue the unfused kernels
    fuse), and accumulated into an on-chip ``[block_r, F]`` aggregation
    tile;
  * the dense transform runs on that tile on chip: one
    ``[block_r, F] @ [F, H]`` MXU matmul + bias + (optional) ReLU, and
    only the ``[block_r, H]`` layer output is ever written back to HBM.

The aggregation intermediate never exists in HBM — per layer that saves
one ``[rows, F]`` write plus one ``[rows, F]`` read against the unfused
pipeline (the AKG/MindSpore CSR-fusion observation applied to the AES
layout; GE-SpMM's coalesced gather is the row-DMA analogue).

The grid is 1-D over row tiles only: the dense transform contracts over
the full feature dimension, so F is not tiled — the layer weights
``[F, H]`` must fit VMEM, which holds for GNN layer widths (the "small
dense transform" regime this kernel targets; :func:`vmem_bytes` counts the
buffers and ``repro.kernels.ops`` refuses a layer over
``VMEM_BUDGET``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import (LANES, TiledFeatures, for_row_groups,
                                  gather_accumulate)

# The kernel runs inside the default scoped VMEM of a v5e core.
VMEM_BUDGET = 16 << 20


def vmem_bytes(block_r: int, feat: int, hidden: int, pack: int = 1) -> int:
    """VMEM the fused layer's buffers take at padded widths F (``feat``)
    and H (``hidden``): one copy each of the grid-invariant ``[F, H]``
    weight and ``[1, H]`` bias blocks, two of the ``[block_r, H]`` output
    block, and the two-slot B-row landing zone of ``F / pack`` words.  At
    F = H = 2560 Mosaic reports a 25.18 MiB scoped allocation for these
    buffers on v5e, against 25.2 MiB counted here."""
    return 4 * (feat * hidden + hidden + 2 * block_r * hidden
                + 2 * (feat // pack))


def _fused_layer_kernel(val_ref, col_ref, live_ref, w_ref, bias_ref, b_ref,
                        out_ref, bsc, sem, *, pack: int, bits, scale: float,
                        x_min: float, relu: bool):
    """grid = (row_tiles,).

    val_ref:  f32[block_r, W]    SMEM  sampled edge weights
    col_ref:  i32[block_r, W]    SMEM  sampled column indices
    live_ref: i32[1, block_r]    SMEM  live width per row
    w_ref:    f32[F, H]          VMEM  layer weights (padded)
    bias_ref: f32[1, H]          VMEM  layer bias (padded)
    b_ref:    [F / (128 * pack), num_nodes, 128] HBM  dense features, f32
              or uint8 packed ``pack`` to a word (``gather.TiledFeatures``)
    out_ref:  f32[block_r, H]    VMEM  layer output tile
    bsc:      VMEM[2, F / (128 * pack), 1, 128]  double-buffered B-row
              landing zone
    sem:      DMA semaphores [2]
    """
    def row_acc(base, i):
        r = base + i
        return gather_accumulate(
            b_ref, bsc, sem, live_ref[0, r], lambda k: col_ref[r, k],
            lambda k: val_ref[r, k], pack=pack, bits=bits, scale=scale,
            x_min=x_min)

    def transform(base, agg):
        # Dense transform epilogue on the on-chip aggregation tile: one
        # MXU matmul per row group, f32-exact; only [group, H] reaches HBM.
        h = jnp.dot(agg, w_ref[...], precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32) + bias_ref[...]
        if relu:
            h = jnp.maximum(h, 0.0)
        out_ref[pl.ds(base, agg.shape[0]), :] = h

    for_row_groups(out_ref.shape[0], row_acc, transform)


@functools.partial(
    jax.jit,
    static_argnames=("block_r", "scale", "x_min", "relu", "interpret"))
def fused_layer(ell_val, ell_col, live_w, b: TiledFeatures, w, bias, *,
                block_r: int = 8, scale=1.0, x_min=0.0, relu: bool = True,
                interpret: bool = True):
    """out[r, :] = act(sum_k ell_val[r, k] * B[ell_col[r, k], :] @ W + bias).

    Inputs must be padded: rows % block_r == 0, H % 128 == 0, and W's rows
    to B's padded features (``repro.kernels.ops`` pads).  A quantized B
    (``b.bits`` set) is dequantized in the gather, ``q * scale + x_min``.
    """
    rows, width = ell_val.shape
    hidden = w.shape[1]
    feat = b.padded_features
    assert rows % block_r == 0 and w.shape[0] == feat and b.width == LANES
    bt = b.tiles

    kernel = functools.partial(
        _fused_layer_kernel, pack=b.pack, scale=scale, x_min=x_min,
        relu=relu, bits=b.bits)
    return pl.pallas_call(
        kernel,
        name="fused_layer",
        grid=(rows // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_r, width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, block_r), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((feat, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_r, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, bt.shape[0], 1, LANES), bt.dtype),  # B rows
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(ell_val, ell_col,
      live_w.astype(jnp.int32).reshape(rows // block_r, 1, block_r), w,
      bias.reshape(1, hidden), bt)
