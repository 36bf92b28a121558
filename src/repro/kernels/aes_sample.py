"""Pallas TPU kernel: standalone AES sampling pre-pass (CSR -> ELL).

The sampling half of Algorithm 1 as its own kernel, producing the ELL
operand the SpMM kernels read.

A row at or under the width W (``_R_THRESHOLDS[0] == 1``) takes Table 1's
first band: one sample of all its ``nnz`` entries from offset 0, so its
ELL row is its CSR run, zero-padded.  Only a row over W is sampled into
the interleaved slots ``i + j * cnt``.  The kernel runs the two apart,
per row, from ``nnz``:

  * A program takes ``block_r`` consecutive rows, whose runs form one
    contiguous CSR span.  The aligned lane-tile rows that cover the
    span's first ``block_r * W`` entries are copied into a VMEM window,
    double-buffered across programs so that block i+1's copy runs while
    block i is expanded.  ``col_ind``/``val`` reach the kernel as
    ``[E / 128, 128]`` HBM arrays: a DMA out of a tiled HBM array may cut
    single rows only when the array is one lane tile wide.
  * A row at or under W is a vector copy: per 128-lane part, two
    lane-tile rows of the window read at the run's dynamic sublane
    offset, rotated by the run's lane offset, selected and masked past
    ``nnz``.  A run that leaves the window (a row after a hub row of the
    same block) first gets its own small DMA of the rows it needs.
  * A row over W keeps the scalar :func:`fused_spmm.sample_row`: its
    slots are staged in SMEM and moved into VMEM with a local DMA, and
    from there take the same vector path.

Rows are expanded eight at a time, so each part is stored as one
``[8, 128]`` tile at an aligned sublane offset: Mosaic refuses a store
whose sublane and lane offsets are both dynamic and not aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_spmm import sample_row
from .gather import (FLAT_TILE, LANES, SUBLANES, check_smem, flat_window,
                     smem_words, vmem_limit)

# CSR entries a program's rows may span at most: ``block_r * W`` stays at
# or under this (as far as ``block_r >= 8`` allows), so the window and
# the output tiles take about 1 MiB of VMEM whatever W is.
BLOCK_SLOTS = 1 << 15
MAX_BLOCK_R = 256
# VMEM the kernel's buffers may take: the default scoped 16 MiB, less
# headroom for Mosaic's own scratch.
VMEM_BUDGET = 12 << 20


def _parts(sh_width: int) -> int:
    return pl.cdiv(sh_width, LANES)


def geometry(rows: int, sh_width: int, block_r: int | None = None):
    """``(block_r, window)`` of a call over ``rows`` rows: rows a program
    takes, and lane-tile rows of its staged CSR window.  The window holds
    ``block_r * W`` entries past the block's first entry, whose aligned
    start lies at most ``FLAT_TILE - 1`` entries before it, so only a row
    after a hub row of its block can leave it."""
    if block_r is None:
        block_r = min(MAX_BLOCK_R,
                      max(SUBLANES, BLOCK_SLOTS // sh_width // SUBLANES
                          * SUBLANES))
    if block_r % SUBLANES:
        raise ValueError(f"block_r {block_r} is not a multiple of {SUBLANES}")
    block_r = min(block_r, pl.cdiv(rows, SUBLANES) * SUBLANES)
    window = pl.cdiv(pl.cdiv(block_r * sh_width + FLAT_TILE - 1, LANES),
                     SUBLANES) * SUBLANES
    return block_r, window


def _stage_rows(window: int, sh_width: int) -> int:
    """Rows of one window slot: the window, and ``parts + 1`` rows for
    each row of a group of eight whose run is copied on its own (a row
    outside the window, or a sampled row's slots)."""
    return window + SUBLANES * (_parts(sh_width) + 1)


def smem_bytes(block_r: int, sh_width: int) -> int:
    """SMEM of :func:`aes_sample`: the double-buffered row-start, row-nnz
    and window-base blocks, the sampled row's slots, its live width, and
    the CSR run stages of :func:`fused_spmm.sample_row`."""
    return 4 * (2 * (2 * smem_words((1, block_r)) + smem_words((1, 2)))
                + 2 * smem_words((_parts(sh_width), LANES))
                + smem_words((1, SUBLANES))
                + 2 * smem_words((flat_window(sh_width),)))


def vmem_bytes(block_r: int, sh_width: int) -> int:
    """VMEM of :func:`aes_sample`: the double-buffered ``[block_r, W]``
    val and col output tiles (lanes padded to 128) and the two slots of
    the col and val windows."""
    _, window = geometry(block_r, sh_width, block_r)
    return 4 * (2 * 2 * block_r * _parts(sh_width) * LANES
                + 2 * 2 * _stage_rows(window, sh_width) * LANES)


def check_fits(sh_width: int, block_r: int | None = None) -> None:
    """Refuse a width whose buffers exceed the SMEM or VMEM budget, at
    the most rows a program takes (a graph of fewer rows takes less)."""
    block_r, _ = geometry(block_r or MAX_BLOCK_R, sh_width, block_r)
    check_smem(smem_bytes(block_r, sh_width), f"aes_sample at width {sh_width}")
    need = vmem_bytes(block_r, sh_width)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"aes_sample at width {sh_width} needs {need} bytes of VMEM, "
            f"over the budget of {VMEM_BUDGET}; use the jax backend")


def fits(sh_width: int) -> bool:
    """Whether :func:`check_fits` admits ``sh_width``."""
    try:
        check_fits(sh_width)
    except ValueError:
        return False
    return True


def row_paths(row_ptr, sh_width: int, block_r: int | None = None):
    """``(whole, sampled, own_dma)``: rows the kernel copies whole, rows
    it samples (over W), and the rows copied whole that leave their
    block's window and take a DMA of their own.  Traceable; the same
    arithmetic as the kernel."""
    rows = row_ptr.shape[0] - 1
    block_r, window = geometry(rows, sh_width, block_r)
    start = row_ptr[:-1]
    nnz = row_ptr[1:] - start
    first = start[(jnp.arange(rows) // block_r) * block_r]
    off = start - first // FLAT_TILE * FLAT_TILE
    sampled = nnz > sh_width
    own = (nnz > 0) & ~sampled & (off + nnz > window * LANES)
    return ((~sampled).sum(), sampled.sum(), own.sum())


def _sample_kernel(meta_ref, rs_ref, nnz_ref, ci_ref, av_ref, ci2_ref,
                   av2_ref, val_out, col_out, win_i, win_f, sh_i, sh_f,
                   live_ref, stage_i, stage_f, sem, wsem, osem,
                   *, sh_width: int, window: int):
    """grid = (row_blocks,), run in order.

    meta_ref:         i32[1, 2]   SMEM  lane-tile row of this block's and
                                        the next block's window
    rs_ref/nnz_ref:   i32[1, block_r]   SMEM  CSR row starts / row nnz
    ci_ref/av_ref:    HBM  CSR col_ind / val, 1-D (the sampled rows)
    ci2_ref/av2_ref:  HBM  the same arrays as ``[E / 128, 128]``
    val_out/col_out:  [block_r, W]      VMEM  the ELL rows
    win_i/win_f:      [2, rows, 128]    VMEM  window slots: the block's
        window, then ``parts + 1`` rows per row of a group of eight
    sh_i/sh_f:        [parts, 128]      SMEM  a sampled row's slots
    live_ref:         [1, 8]            SMEM  live widths of sampled rows
    stage_i/stage_f:  SMEM  CSR run stages of ``sample_row``
    """
    i = pl.program_id(0)
    slot = i % 2
    parts = _parts(sh_width)
    full = sh_width // LANES

    def window_copies(row, s):
        return [pltpu.make_async_copy(src.at[pl.ds(row, window)],
                                      dst.at[s, pl.ds(0, window)],
                                      wsem.at[s, k])
                for k, (src, dst) in enumerate(((ci2_ref, win_i),
                                                (av2_ref, win_f)))]

    @pl.when(i == 0)
    def _():
        for cp in window_copies(meta_ref[0, 0], 0):
            cp.start()

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        for cp in window_copies(meta_ref[0, 1], 1 - slot):
            cp.start()

    for cp in window_copies(meta_ref[0, 0], slot):
        cp.wait()

    first = meta_ref[0, 0] * LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    rid = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def put(s, c, v):
        sh_i[s // LANES, s % LANES] = c
        sh_f[s // LANES, s % LANES] = v

    def classify(row):
        """Row ``row``'s run: its start, nnz, offset in the window, and
        whether it is sampled (over W) or copied whole from a DMA of its
        own (it leaves the window)."""
        start = rs_ref[0, row]
        nnz = nnz_ref[0, row]
        off = start - first
        sampled = nnz > sh_width
        own = (nnz > 0) & jnp.logical_not(sampled) \
            & (off + nnz > window * LANES)
        return start, nnz, off, sampled, own

    def move(r, base):
        """Stage group row ``r``'s run in its tail rows of the window
        slot when it is sampled or leaves the window."""
        start, nnz, _, sampled, own = classify(base + r)
        tail = window + r * (parts + 1)

        def copy(pairs, at):
            copies = [pltpu.make_async_copy(src, dst.at[slot, at], osem.at[k])
                      for k, (src, dst) in enumerate(pairs)]
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()

        @pl.when(own)
        def _():
            rows = pl.ds(start // LANES, parts + 1)
            copy(((ci2_ref.at[rows], win_i), (av2_ref.at[rows], win_f)),
                 pl.ds(tail, parts + 1))

        @pl.when(sampled)
        def _():
            live_ref[0, r] = sample_row(ci_ref, av_ref, stage_i, stage_f,
                                        sem, start, nnz, sh_width, put)
            copy(((sh_i, win_i), (sh_f, win_f)), pl.ds(tail, parts))

        return base

    def locate(r, start, nnz, off, sampled, own):
        """(q, m, lim) of group row ``r``: its run starts at lane ``m`` of
        window row ``q`` and has ``lim`` live slots."""
        q = jnp.where(own | sampled, window + r * (parts + 1),
                      jnp.clip(off // LANES, 0, window))
        m = jnp.where(sampled, 0, start % LANES)
        lim = jnp.where(sampled, live_ref[0, r], nnz)
        return q, m, lim

    def group(g, carry):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        # Rows that need a copy of their own are rare: one loop, entered
        # only by a group that holds one, emits the scalar sampler and the
        # DMAs once rather than in each of the eight unrolled rows.
        rows = [classify(base + r) for r in range(SUBLANES)]

        @pl.when(functools.reduce(jnp.logical_or,
                                  [c[3] | c[4] for c in rows]))
        def _():
            jax.lax.fori_loop(0, SUBLANES, move, base)

        locs = [locate(r, *c) for r, c in enumerate(rows)]

        def part(p, width):
            tiles = []
            for win in (win_f, win_i):
                tile = jnp.zeros((SUBLANES, LANES), win.dtype)
                for r, (q, m, lim) in enumerate(locs):
                    shift = (LANES - m) % LANES
                    lo = pltpu.roll(win[slot, pl.ds(q + p, 1), :], shift, 1)
                    hi = pltpu.roll(win[slot, pl.ds(q + p + 1, 1), :],
                                    shift, 1)
                    v = jnp.where(lane < LANES - m, lo, hi)
                    v = jnp.where(lane + p * LANES < lim, v, 0)
                    tile = jnp.where(rid == r, v, tile)
                tiles.append(tile[:, :width])
            at = p * LANES if isinstance(p, int) \
                else pl.multiple_of(p * LANES, LANES)
            col = pl.ds(at, width)
            val_out[pl.ds(base, SUBLANES), col] = tiles[0]
            col_out[pl.ds(base, SUBLANES), col] = tiles[1]

        def full_part(p, c):
            part(p, LANES)
            return c

        if full == 1:
            part(0, LANES)
        elif full > 1:
            jax.lax.fori_loop(0, full, full_part, 0)
        if full < parts:
            part(full, sh_width - full * LANES)
        return carry

    jax.lax.fori_loop(0, val_out.shape[0] // SUBLANES, group, 0)


@functools.partial(
    jax.jit, static_argnames=("sh_width", "block_r", "interpret"))
def aes_sample(row_ptr, col_ind, val, *, sh_width: int,
               block_r: int | None = None, interpret: bool = True):
    """Returns (ell_val, ell_col) of shape [rows, sh_width]: the AES
    sample of the CSR matrix ``(row_ptr, col_ind, val)``, slot for slot
    ``core.sampling.sample_csr_to_ell``.  ``block_r`` (a multiple of 8)
    overrides the rows a program takes."""
    rows = row_ptr.shape[0] - 1
    block_r, window = geometry(rows, sh_width, block_r)
    padded = pl.cdiv(rows, block_r) * block_r
    row_ptr = row_ptr.astype(jnp.int32)
    start = jnp.pad(row_ptr[:-1], (0, padded - rows), mode="edge")
    nnz = jnp.pad(row_ptr[1:] - row_ptr[:-1], (0, padded - rows))
    lanes0 = start[::block_r] // FLAT_TILE * (FLAT_TILE // LANES)
    meta = jnp.stack([lanes0, jnp.append(lanes0[1:], lanes0[-1])], axis=1)
    # Past the last entry: a window, an own run copy and a sample DMA
    # (``flat_window``) each read at most this far.
    tail = max(window * LANES, (_parts(sh_width) + 1) * LANES,
               flat_window(sh_width))
    length = (pl.cdiv(col_ind.shape[0], FLAT_TILE) * FLAT_TILE
              + pl.cdiv(tail, FLAT_TILE) * FLAT_TILE)
    ci = jnp.pad(col_ind.astype(jnp.int32), (0, length - col_ind.shape[0]))
    av = jnp.pad(val.astype(jnp.float32), (0, length - val.shape[0]))
    blocks = padded // block_r
    kernel = functools.partial(_sample_kernel, sh_width=sh_width,
                               window=window)
    stage_rows = _stage_rows(window, sh_width)
    parts = _parts(sh_width)
    smem_row = pl.BlockSpec((None, 1, block_r), lambda i: (i, 0, 0),
                            memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        name="aes_sample",
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((None, 1, 2), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            smem_row, smem_row,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((block_r, sh_width), lambda i: (i, 0)),
            pl.BlockSpec((block_r, sh_width), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, sh_width), jnp.float32),
            jax.ShapeDtypeStruct((rows, sh_width), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, stage_rows, LANES), jnp.int32),
            pltpu.VMEM((2, stage_rows, LANES), jnp.float32),
            pltpu.SMEM((parts, LANES), jnp.int32),
            pltpu.SMEM((parts, LANES), jnp.float32),
            pltpu.SMEM((1, SUBLANES), jnp.int32),
            pltpu.SMEM((flat_window(sh_width),), jnp.int32),
            pltpu.SMEM((flat_window(sh_width),), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit(vmem_bytes(block_r, sh_width))),
    )(meta.reshape(blocks, 1, 2), start.reshape(blocks, 1, block_r),
      nnz.reshape(blocks, 1, block_r), ci, av,
      ci.reshape(-1, LANES), av.reshape(-1, LANES))
