"""Pallas TPU kernel: INT8 -> Float32 feature dequantization (paper Eq. 2).

Elementwise VPU kernel over (block_n, block_f) VMEM tiles: the paper runs
dequantization "in parallel on the GPU end" right after the quantized
features land on-device; here it is a tiled TPU kernel (~2 ms on the paper's
GPU; bandwidth-bound on TPU: 1 byte in, 4 bytes out per element).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def dequant_epilogue(q, scale, x_min, dtype=jnp.float32):
    """Eq. 2 as a reusable in-kernel epilogue: ``q * scale + x_min``.

    Shared by this standalone kernel and the fused-dequant gathers in
    ``ell_spmm.py`` (both the fixed-width and the block-dispatched SpMM),
    so the dequantization math has exactly one home.  Mosaic converts
    unsigned integers to float only through int32.
    """
    return q.astype(jnp.int32).astype(dtype) * scale + x_min


def _dequant_kernel(q_ref, out_ref, *, scale: float, x_min: float):
    out_ref[...] = dequant_epilogue(q_ref[...], scale, x_min)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_n", "block_f", "interpret", "scale", "x_min"))
def dequantize(q, *, scale: float, x_min: float, bits: int = 8,
               block_n: int = 256, block_f: int = 128,
               interpret: bool = True):
    """x^ = q * scale + x_min with scale = (x_max - x_min) / (2^bits - 1).

    ``q`` must be padded to (block_n, block_f) multiples (ops.py pads).
    """
    n, f = q.shape
    assert n % block_n == 0 and f % block_f == 0
    grid = (n // block_n, f // block_f)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, scale=scale, x_min=x_min),
        name="dequantize",
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, block_f), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_n, block_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, f), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q)
