"""Pallas TPU kernel: standalone AES sampling pre-pass (CSR -> ELL).

The sampling half of Algorithm 1 as its own kernel, for pipelines that
sample once and reuse the ELL across layers (both GCN layers aggregate with
the same A, so sampling once amortizes — the paper's kernel resamples per
call; this is a beyond-paper amortization, see EXPERIMENTS.md §Perf).

Each row is sampled by the same ``sample_row`` the fused kernel runs; the
staged slots land in SMEM output tiles and are written out to HBM in ELL
layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_spmm import sample_row
from .gather import edge_tile_smem_bytes, flat_window, smem_words


def smem_bytes(block_r: int, sh_width: int) -> int:
    """SMEM of :func:`aes_sample`: its double-buffered ``[block_r,
    sh_width]`` output tiles and row blocks (the shape of an ELL kernel's
    edge list), and the CSR run stages."""
    return edge_tile_smem_bytes(block_r, sh_width) \
        + 4 * (2 * smem_words((flat_window(sh_width),))
               + 2 * smem_words((1, block_r)))


def _sample_kernel(rs_ref, nnz_ref, ci_ref, av_ref, val_out, col_out,
                   stage_i, stage_f, sem, *, sh_width: int):
    """grid = (row_tiles,).

    rs_ref/nnz_ref:   i32[1, block_r]        SMEM  CSR row starts / row nnz
    ci_ref/av_ref:    HBM  full CSR col_ind / val arrays (padded)
    val_out/col_out:  [block_r, sh_width]    SMEM  the sampled ELL rows,
        written slot by slot with scalar stores and copied out whole by the
        output pipeline
    stage_i/stage_f:  SMEM  aligned landing windows of one sample's CSR run
    """
    def row_body(r, carry):
        def zero(j, c):
            val_out[r, j] = jnp.float32(0)
            col_out[r, j] = jnp.int32(0)
            return c

        jax.lax.fori_loop(0, sh_width, zero, 0)

        def put(slot, c, v):
            col_out[r, slot] = c
            val_out[r, slot] = v

        sample_row(ci_ref, av_ref, stage_i, stage_f, sem, rs_ref[0, r],
                   nnz_ref[0, r], sh_width, put)
        return carry

    jax.lax.fori_loop(0, val_out.shape[0], row_body, 0)


@functools.partial(
    jax.jit, static_argnames=("sh_width", "block_r", "interpret"))
def aes_sample(row_start, row_nnz, col_ind, val, *, sh_width: int,
               block_r: int = 8, interpret: bool = True):
    """Returns (ell_val, ell_col) of shape [rows, sh_width].

    ``col_ind``/``val`` must carry >= ``flat_window(sh_width)`` padding
    elements at the end (the fixed-size sample DMA over-reads past a row's
    end; over-read values are masked by the slot layout, padding only
    prevents OOB).
    """
    rows = row_start.shape[0]
    assert rows % block_r == 0
    kernel = functools.partial(_sample_kernel, sh_width=sh_width)
    stage = flat_window(sh_width)
    return pl.pallas_call(
        kernel,
        name="aes_sample",
        grid=(rows // block_r,),
        in_specs=[
            pl.BlockSpec((None, 1, block_r), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, 1, block_r), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_r, sh_width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_r, sh_width), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, sh_width), jnp.float32),
            jax.ShapeDtypeStruct((rows, sh_width), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.SMEM((stage,), jnp.int32),
            pltpu.SMEM((stage,), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(row_start.astype(jnp.int32).reshape(rows // block_r, 1, block_r),
      row_nnz.astype(jnp.int32).reshape(rows // block_r, 1, block_r),
      col_ind, val)
