"""jit'd public wrappers around the Pallas kernels.

Handles shape padding (row tiles, feature tiles, CSR over-read guards),
backend dispatch (interpret=True on CPU — the kernels target TPU), and
exposes a uniform signature over CSR/ELL inputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import CSR, ELL, BlockELL

from . import aes_sample as _aes_sample_mod
from . import ell_spmm as _ell_mod
from . import fused_layer as _fused_layer_mod
from . import fused_spmm as _fused_mod
from .dequant import dequantize as _dequant_kernel
from .gather import (LANES, SMEM_BUDGET, block_tail, check_smem,
                     edge_tile_smem_bytes, flat_window, tiled)


def ell_fits_smem(width: int, *, sampled: bool = False,
                  block_r: int = 8) -> bool:
    """Whether an ELL operand ``width`` slots wide fits the SMEM of the
    ELL kernels (``ell_spmm``, ``fused_layer_spmm``) at row tile
    ``block_r`` — and, with ``sampled``, the buffers of the ``aes_sample``
    kernel that builds it too.  A wider one runs on the jax backend only."""
    return edge_tile_smem_bytes(block_r, width) <= SMEM_BUDGET and (
        not sampled or _aes_sample_mod.fits(width))


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _quant_kw(quantized_meta) -> dict:
    if quantized_meta is None:
        return {}
    scale, x_min = quantized_meta
    return dict(scale=float(scale), x_min=float(x_min))


def ell_spmm(ell: ELL, b, live_w=None, *, block_r: int = 8,
             block_f: int = 128, quantized_meta=None, interpret=None):
    """Pallas ELL SpMM with padding.

    Args:
      ell: sampled operand; ``ell.val`` f32[rows, W], ``ell.col``
        int32[rows, W] (dead slots zeroed, live slots a contiguous prefix).
      b: dense operand [num_nodes, feat] — f32, or uint8 when
        ``quantized_meta`` is given — or a ``gather.TiledFeatures``.
      live_w: optional int32[rows] live-prefix lengths; derived from the
        zero sentinel when omitted.
      block_r / block_f: Pallas tile sizes (rows and feat are padded up to
        multiples of these; the padding is sliced off the result).  An
        ELL wider than the kernel's SMEM holds at ``block_r`` is refused
        (``ell_fits_smem``).
      quantized_meta: ``(scale, x_min)`` enables the fused-dequant gather
        (beyond-paper int8 path; B must then be uint8).
      interpret: force Pallas interpret mode (default: interpret unless
        running on a real TPU).

    Returns:
      f32[rows, feat] with ``C[r] = sum_k ell.val[r, k] * B[ell.col[r, k]]``.
    """
    interpret = _interpret_default() if interpret is None else interpret
    rows, width = ell.val.shape
    check_smem(edge_tile_smem_bytes(block_r, width),
               f"ell_spmm at width {width}")
    bt = tiled(b, block_f)
    if obs.enabled():
        if _ell_mod.ring_gather(bt):
            obs.count("spmm.ring_calls")
            obs.count("spmm.ring_tiles", bt.tiles.shape[0])
        else:
            obs.count("spmm.tile_calls")
    if live_w is None:
        from repro.core.graph import ell_live_widths

        live_w = ell_live_widths(ell.val, ell.col)
    val = _pad_to(ell.val, block_r, 0)
    col = _pad_to(ell.col, block_r, 0)
    lw = _pad_to(live_w, block_r, 0)
    out = _ell_mod.ell_spmm(val, col, lw, bt, block_r=block_r,
                            interpret=interpret,
                            **_quant_kw(quantized_meta))
    return out[:rows, :bt.num_features]


def block_ell_spmm(bell: BlockELL, b, *, block_f: int = 128,
                   quantized_meta=None, buckets=None, interpret=None):
    """Block-dispatched Pallas SpMM over a mixed-width BlockELL operand,
    launched once per width bucket.

    One Pallas program per (row block x feature tile); each program reads
    its own (offset, width) from the block table, so tail blocks tuned to a
    narrow width do proportionally less DMA and accumulation work.  Blocks
    are grouped into width buckets and each bucket gets its own launch with
    a static row-DMA width equal to the bucket max — narrow blocks stop
    issuing max-width staging DMAs.

    Args:
      bell: the stitched mixed-width operand (see ``core.graph.BlockELL``).
      b: dense operand [num_nodes, feat] — f32, or the quantized storage
        dtype (uint8/uint16) when ``quantized_meta`` is given — or a
        ``gather.TiledFeatures``.
      block_f: feature-tile size (feat is padded up to a multiple).
      quantized_meta: ``(scale, x_min)`` enables the fused-dequant gather
        (Eq. 2 fused into the B-row fetch; B must then be quantized).
      buckets: explicit width-bucket partition ``((bucket_w, block_ids),
        ...)`` as produced by ``core.graph.partition_width_buckets`` —
        a tuned ``BlockedPlan`` passes its cached bucket table.  Default:
        computed here from ``bell.widths``.  A *partial* partition (not
        covering every block) is allowed — uncovered blocks' output rows
        stay zero — which the tuner's per-bucket microbenchmarks use to
        time one bucket in isolation.
      interpret: force Pallas interpret mode (default: interpret off-TPU).

    Returns:
      f32[bell.num_rows, feat] — padded trailing rows sliced off.
    """
    from repro.core.graph import partition_width_buckets

    interpret = _interpret_default() if interpret is None else interpret
    if buckets is None:
        buckets = partition_width_buckets(bell.widths)
    for bucket_w, _ in buckets:
        check_smem(_ell_mod.block_smem_bytes(bell.block_rows, bucket_w),
                   f"block_ell_spmm at width {bucket_w}")
    bt = tiled(b, block_f)
    feat = bt.num_features
    # The fixed-size staging DMA over-reads up to ``block_tail`` of the
    # global max_width past the last segment; the stitcher pre-pads the
    # flat arrays for this (plans built by other means fall back to a
    # per-call pad).
    need = bell.total_slots + block_tail(bell.max_width)
    if bell.val.shape[0] >= need:
        val_flat, col_flat = bell.val, bell.col
    else:
        short = need - bell.val.shape[0]
        val_flat = jnp.pad(bell.val, (0, short))
        col_flat = jnp.pad(bell.col, (0, short))
    kw = _quant_kw(quantized_meta)

    offs = bell.slot_offsets()
    br = bell.block_rows
    live2d = bell.live_w.reshape(bell.num_blocks, br)
    results, order = [], []
    for bucket_w, ids in buckets:
        table = jnp.asarray([[offs[i], bell.widths[i]] for i in ids],
                            jnp.int32)
        lw = bell.live_w if ids == tuple(range(bell.num_blocks)) \
            else live2d[jnp.asarray(ids, jnp.int32)].reshape(-1)
        results.append(_ell_mod.block_ell_spmm(
            table, lw, val_flat, col_flat, bt, block_rows=br,
            max_w=bucket_w, interpret=interpret, **kw))
        order.extend(ids)

    # Reassembly costs one copy, not one full-output scatter per bucket:
    # concatenate the per-bucket results (block order = `order`) and map
    # back to row order with a single static gather — or, for a partial
    # partition (bucket microbenchmarks), one scatter into zeros.
    stacked = results[0] if len(results) == 1 \
        else jnp.concatenate(results, axis=0)
    if order == list(range(bell.num_blocks)):
        return stacked[:bell.num_rows, :feat]
    if len(order) == bell.num_blocks:
        pos = {b: p for p, b in enumerate(order)}
        gather = np.concatenate(
            [np.arange(pos[b] * br, (pos[b] + 1) * br)
             for b in range(bell.num_blocks)])
        return stacked[jnp.asarray(gather, jnp.int32)][:bell.num_rows, :feat]
    rows_idx = np.concatenate(
        [np.arange(i * br, (i + 1) * br) for i in order])
    out = jnp.zeros((bell.padded_rows, bt.padded_features), jnp.float32)
    out = out.at[jnp.asarray(rows_idx, jnp.int32)].set(stacked)
    return out[:bell.num_rows, :feat]


def fused_layer_spmm(ell: ELL, b, w, bias, live_w=None, *, relu: bool = True,
                     block_r: int = 8, quantized_meta=None, interpret=None):
    """Pallas fused GNN layer: gather + (dequant) + SpMM + dense transform
    + activation in one launch — the aggregation intermediate never
    round-trips HBM.

    Args:
      ell: sampled operand (same contract as :func:`ell_spmm`).
      b: dense operand [num_nodes, feat] — f32, or uint8 when
        ``quantized_meta`` is given — or a ``gather.TiledFeatures``.
      w: layer weights f32[feat, hidden].
      bias: layer bias f32[hidden].
      live_w: optional int32[rows] live-prefix lengths.
      relu: apply ReLU after the bias add (False for a logits layer).
      block_r: row-tile size.
      quantized_meta: ``(scale, x_min)`` enables the fused-dequant gather.
      interpret: force Pallas interpret mode (default: interpret off-TPU).

    A layer whose buffers exceed the kernel's VMEM budget, or an ELL wider
    than its SMEM holds, is refused with a ``ValueError``.

    Returns:
      f32[rows, hidden] with
      ``out[r] = act(sum_k ell.val[r, k] * B[ell.col[r, k]] @ W + bias)``.
    """
    interpret = _interpret_default() if interpret is None else interpret
    rows, width = ell.val.shape
    hidden = w.shape[1]
    check_smem(edge_tile_smem_bytes(block_r, width),
               f"fused_layer_spmm at width {width}")
    bt = tiled(b, LANES)
    feat = bt.num_features
    if w.shape[0] != feat:
        raise ValueError(
            f"weight rows {w.shape[0]} != operand features {feat}")
    # B's features are padded to whole tiles of (packed) words and H to
    # whole lane tiles; padded W rows/columns are zero, so the padding
    # contributes nothing to the matmul (a quantized B's padded features
    # dequantize to x_min, but the matching W rows are zero).
    wp = _pad_to(_pad_to(w, bt.padded_features, 0), LANES, 1)
    biasp = _pad_to(bias.reshape(-1), LANES, 0)
    need = _fused_layer_mod.vmem_bytes(block_r, bt.padded_features,
                                       wp.shape[1], bt.pack)
    if need > _fused_layer_mod.VMEM_BUDGET:
        raise ValueError(
            f"fused layer dims F={feat}, H={hidden} need {need} bytes of "
            f"VMEM, over the kernel's budget of "
            f"{_fused_layer_mod.VMEM_BUDGET}; use the unfused path")
    if live_w is None:
        from repro.core.graph import ell_live_widths

        live_w = ell_live_widths(ell.val, ell.col)
    val = _pad_to(ell.val, block_r, 0)
    col = _pad_to(ell.col, block_r, 0)
    lw = _pad_to(live_w, block_r, 0)
    out = _fused_layer_mod.fused_layer(val, col, lw, bt, wp, biasp,
                                       block_r=block_r, relu=relu,
                                       interpret=interpret,
                                       **_quant_kw(quantized_meta))
    return out[:rows, :hidden]


def aes_sample(csr: CSR, sh_width: int, *, block_r=None,
               interpret=None) -> ELL:
    """Pallas AES sampling pre-pass: CSR -> ELL(width=sh_width).

    Args:
      csr: source matrix.
      sh_width: static ELL width (the paper's shared-memory W knob).
      block_r: rows per Pallas program, a multiple of 8 (default: the
        kernel's own, ``aes_sample.geometry``).
      interpret: force Pallas interpret mode (default: interpret off-TPU).

    A width whose buffers exceed the kernel's SMEM or VMEM budget is
    refused with a ``ValueError``.

    Returns:
      ``ELL`` with ``val`` f32[num_rows, sh_width], ``col``
      int32[num_rows, sh_width], dead slots zeroed.
    """
    interpret = _interpret_default() if interpret is None else interpret
    _aes_sample_mod.check_fits(sh_width, block_r)
    val, col = _aes_sample_mod.aes_sample(
        csr.row_ptr, csr.col_ind, csr.val, sh_width=sh_width,
        block_r=block_r, interpret=interpret)
    return ELL(val, col, csr.num_cols)


def fused_aes_spmm(csr: CSR, b, sh_width: int, *, block_r: int = 8,
                   block_f: int = 128, interpret=None):
    """Single-kernel AES-SpMM (paper Alg. 1): sample + multiply fused.

    Args:
      csr: source matrix (arrays padded internally for the run DMA).
      b: dense operand f32[num_nodes, feat].
      sh_width: static shared-memory width W.
      block_r / block_f: Pallas tile sizes (padded, then sliced off).
      interpret: force Pallas interpret mode (default: interpret off-TPU).

    Returns:
      f32[num_rows, feat] — AES-sampled aggregation, no intermediate ELL
      materialized in HBM.
    """
    interpret = _interpret_default() if interpret is None else interpret
    check_smem(_fused_mod.smem_bytes(block_r, sh_width),
               f"fused_aes_spmm at width {sh_width}")
    rows = csr.num_rows
    bt = tiled(b, block_f)
    row_start = _pad_to(csr.row_ptr[:-1], block_r, 0)
    row_nnz = _pad_to(csr.row_nnz(), block_r, 0)
    ci = jnp.pad(csr.col_ind, (0, flat_window(sh_width)))
    av = jnp.pad(csr.val, (0, flat_window(sh_width)))
    out = _fused_mod.fused_aes_spmm(row_start, row_nnz, ci, av, bt,
                                    sh_width=sh_width, block_r=block_r,
                                    interpret=interpret)
    return out[:rows, :bt.num_features]


def dequantize(q, scale, x_min, *, bits: int = 8, block_n: int = 256,
               block_f: int = 128, interpret=None):
    """Pallas dequantization (paper Eq. 2): ``q * scale + x_min``.

    Args:
      q: quantized matrix uint8/uint16[n, f].
      scale / x_min: the affine dequant constants.
      bits: source bit width (8 or 16).
      block_n / block_f: Pallas tile sizes (padded, then sliced off).
      interpret: force Pallas interpret mode (default: interpret off-TPU).

    Returns f32[n, f].
    """
    interpret = _interpret_default() if interpret is None else interpret
    n, f = q.shape
    qp = _pad_to(_pad_to(q, block_n, 0), block_f, 1)
    out = _dequant_kernel(qp, scale=float(scale), x_min=float(x_min),
                          bits=bits, block_n=block_n, block_f=block_f,
                          interpret=interpret)
    return out[:n, :f]
