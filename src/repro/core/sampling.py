"""Adaptive edge-sampling strategy (AES) — the paper's core contribution.

Implements, bit-exactly and fully vectorized:

  * the strategy table (paper Table 1) mapping ``R = row_nnz / W`` to the
    sampling granularity ``N`` (consecutive elements per sample) and the
    number of samples ``sample_cnt``;
  * the hash function (paper Eq. 3)
    ``start_ind = (current_ind * 1429) mod (row_nnz - N + 1)``;
  * the strided shared-memory slot layout of Algorithm 1 lines 10-12:
    element ``j`` of sample ``i`` lands in slot ``i + j * sample_cnt``.

The sampler converts an irregular CSR matrix into a *regular* ELL layout of
width ``sh_width`` — the TPU-native analogue of the paper's shared-memory
staging (see DESIGN.md §2).  Duplicate edges arising from overlapping hash
windows are kept, exactly as the GPU kernel keeps them.

Also provides the two ES-SpMM baseline strategies the paper compares against:
AFS (accuracy-first, N=1 uniform stride) and SFS (speed-first, first-W
contiguous block).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

PRIME_NUM = 1429  # paper §3.3: "prime_num is set to 1429"

# Strategy table thresholds on R = row_nnz / W (paper Table 1).  Expressed as
# integer comparisons row_nnz <= k * W so the whole selector is exact and
# branch-free (no float division).
_R_THRESHOLDS = (1, 2, 36, 54)
# (N divisor of W, sample_cnt) for each band above R=1.
_BANDS = ((4, 4), (8, 8), (16, 16), (32, 32))
# Samples a row takes in each band above R=1, which names the band.
BAND_SAMPLES = tuple(c for _, c in _BANDS)


class SampleStrategy(NamedTuple):
    """Per-row strategy: pytree of int32 arrays, one entry per row."""

    W: jax.Array           # effective width  = min(row_nnz, sh_width)
    N: jax.Array           # consecutive elements per sample (>= 1)
    sample_cnt: jax.Array  # number of samples (<= W)


def get_sample_strategy(row_nnz: jax.Array, sh_width: int) -> SampleStrategy:
    """Vectorized ``getSampleStrategy`` (Alg. 1 line 6 + Table 1).

    Args:
      row_nnz: int32[rows] non-zeros per row.
      sh_width: static shared-memory width (the paper's ``W`` knob).

    Returns per-row ``(W, N, sample_cnt)`` with the paper's clamps
    ``N >= 1`` and ``sample_cnt <= W`` applied.
    """
    row_nnz = row_nnz.astype(jnp.int32)
    W = jnp.minimum(row_nnz, sh_width)

    # Band selection via integer comparisons: R <= k  <=>  row_nnz <= k * W.
    # For row_nnz <= sh_width we have W = row_nnz, i.e. R = 1 (take-all band).
    conds = [row_nnz <= t * W for t in _R_THRESHOLDS]
    n_vals = [row_nnz] + [W // d for (d, _) in _BANDS]
    c_vals = [jnp.ones_like(W)] + [jnp.full_like(W, c) for (_, c) in _BANDS]
    N = jnp.select(conds + [jnp.full_like(conds[0], True)], n_vals[:1] + n_vals[1:])
    cnt = jnp.select(conds + [jnp.full_like(conds[0], True)], c_vals[:1] + c_vals[1:])

    # Paper: "N constrained to at least 1 and sample_cnt to at most W".
    N = jnp.maximum(N, 1)
    cnt = jnp.minimum(cnt, jnp.maximum(W, 1))
    return SampleStrategy(W=W, N=N, sample_cnt=cnt)


def sampled_rows(row_ptr: jax.Array, sh_width: int):
    """``(runs, *bands)`` of the rows over ``sh_width``, the rows AES
    samples: their samples (one CSR run each, which the Pallas sampler
    fetches with a DMA of its own), and those rows in each band of Table 1
    above R = 1, in the order of :data:`BAND_SAMPLES`.  Traceable."""
    nnz = row_ptr[1:] - row_ptr[:-1]
    over = nnz > sh_width
    runs = jnp.where(over, get_sample_strategy(nnz, sh_width).sample_cnt,
                     0).sum()
    band = sum((nnz > t * sh_width).astype(jnp.int32)
               for t in _R_THRESHOLDS[1:])
    return (runs,) + tuple((over & (band == k)).sum()
                           for k in range(len(BAND_SAMPLES)))


def hash_start_ind(sample_idx: jax.Array, row_nnz: jax.Array, N: jax.Array) -> jax.Array:
    """Paper Eq. 3: ``(current_ind * prime) mod (row_nnz - N + 1)``.

    The modulus is clamped to >= 1 so empty rows are safe; their slots are
    masked out by the caller anyway.
    """
    span = jnp.maximum(row_nnz - N + 1, 1)
    return (sample_idx * PRIME_NUM) % span


def slot_offsets(sh_width: int, strat: SampleStrategy, row_nnz: jax.Array):
    """Compute, for every shared-memory slot ``s`` in [0, sh_width), the CSR
    offset (relative to the row start) it samples, plus a validity mask.

    Inverts the strided layout of Alg. 1: slot ``s`` holds element
    ``j = s // sample_cnt`` of sample ``i = s % sample_cnt``; a slot is live
    iff ``j < N`` (equivalently ``s < N * sample_cnt``).

    Shapes: strat fields are ``[rows]``; returns ``offsets, valid`` of shape
    ``[rows, sh_width]``.
    """
    s = jnp.arange(sh_width, dtype=jnp.int32)[None, :]          # [1, W]
    cnt = strat.sample_cnt[:, None]                              # [rows, 1]
    N = strat.N[:, None]
    nnz = row_nnz.astype(jnp.int32)[:, None]

    i = s % cnt
    j = s // cnt
    start = hash_start_ind(i, nnz, N)
    off = start + j
    valid = (s < N * cnt) & (off < nnz) & (nnz > 0)
    return off, valid


@functools.partial(jax.jit, static_argnames=("sh_width",))
def sample_csr_to_ell(
    row_ptr: jax.Array,
    col_ind: jax.Array,
    val: jax.Array,
    sh_width: int,
):
    """AES sampling pre-pass: CSR -> ELL(width=sh_width).

    Pure-JAX vectorized implementation of Alg. 1 lines 2-14 across all rows
    at once (the GPU kernel parallelizes the same math across thread blocks).

    Returns ``(ell_val[rows, sh_width], ell_col[rows, sh_width])`` with dead
    slots zeroed (val=0 makes them exact no-ops in the SpMM accumulation).
    """
    rows = row_ptr.shape[0] - 1
    if col_ind.shape[0] == 0:  # empty graph: all slots dead
        return (jnp.zeros((rows, sh_width), val.dtype),
                jnp.zeros((rows, sh_width), jnp.int32))
    row_nnz = (row_ptr[1:] - row_ptr[:-1]).astype(jnp.int32)
    strat = get_sample_strategy(row_nnz, sh_width)
    off, valid = slot_offsets(sh_width, strat, row_nnz)

    gidx = row_ptr[:-1, None].astype(jnp.int32) + off
    gidx = jnp.clip(gidx, 0, col_ind.shape[0] - 1)
    ell_col = jnp.where(valid, col_ind[gidx], 0).astype(jnp.int32)
    ell_val = jnp.where(valid, val[gidx], 0).astype(val.dtype)
    return ell_val, ell_col


# ----------------------------------------------------------------------------
# ES-SpMM baseline strategies (paper §2.4 / §4.1 baselines).
# ----------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sh_width",))
def sample_csr_to_ell_afs(row_ptr, col_ind, val, sh_width: int):
    """ES-SpMM accuracy-first strategy: W elements at uniform stride.

    Slot s of a row with row_nnz > W samples offset ``floor(s * row_nnz / W)``
    — fine-grained (N=1), uniform distribution, index math per element
    (the paper's reason AFS is slow on GPU).
    """
    rows = row_ptr.shape[0] - 1
    if col_ind.shape[0] == 0:
        return (jnp.zeros((rows, sh_width), val.dtype),
                jnp.zeros((rows, sh_width), jnp.int32))
    row_nnz = (row_ptr[1:] - row_ptr[:-1]).astype(jnp.int32)
    s = jnp.arange(sh_width, dtype=jnp.int32)[None, :]
    nnz = row_nnz[:, None]
    off = jnp.where(nnz > sh_width, (s * nnz) // sh_width, s)
    valid = (s < jnp.minimum(nnz, sh_width)) & (nnz > 0)
    gidx = jnp.clip(row_ptr[:-1, None].astype(jnp.int32) + off, 0, col_ind.shape[0] - 1)
    return (
        jnp.where(valid, val[gidx], 0).astype(val.dtype),
        jnp.where(valid, col_ind[gidx], 0).astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("sh_width",))
def sample_csr_to_ell_sfs(row_ptr, col_ind, val, sh_width: int):
    """ES-SpMM speed-first strategy: the first W elements of each row
    ("simply judging boundaries") — fast, but concentrated edge distribution.
    """
    rows = row_ptr.shape[0] - 1
    if col_ind.shape[0] == 0:
        return (jnp.zeros((rows, sh_width), val.dtype),
                jnp.zeros((rows, sh_width), jnp.int32))
    row_nnz = (row_ptr[1:] - row_ptr[:-1]).astype(jnp.int32)
    s = jnp.arange(sh_width, dtype=jnp.int32)[None, :]
    valid = (s < jnp.minimum(row_nnz[:, None], sh_width)) & (row_nnz[:, None] > 0)
    gidx = jnp.clip(row_ptr[:-1, None].astype(jnp.int32) + s, 0, col_ind.shape[0] - 1)
    return (
        jnp.where(valid, val[gidx], 0).astype(val.dtype),
        jnp.where(valid, col_ind[gidx], 0).astype(jnp.int32),
    )


STRATEGIES = {
    "aes": sample_csr_to_ell,
    "afs": sample_csr_to_ell_afs,
    "sfs": sample_csr_to_ell_sfs,
}


# ----------------------------------------------------------------------------
# Blocked sampling: one (strategy, width) per fixed-size row block.
# ----------------------------------------------------------------------------

def sample_block_segment(csr, row_nnz_host, b: int, strat: str, width: int,
                         block_rows: int):
    """Sample one row block of a CSR into a padded ELL segment.

    The per-block body of :func:`sample_csr_to_block_ell`, factored out so
    the incremental patcher (``repro.tuning.incremental``) produces segments
    bit-identical to a cold stitch of the same ``(strategy, width)`` — each
    sampler sees the global ``col_ind``/``val`` arrays through the sliced
    ``row_ptr``, so only the block's own row content matters.

    Args:
      csr: the source matrix.
      row_nnz_host: host int array of per-row nnz (hoisted by the caller).
      b: block index.
      strat: key of :data:`STRATEGIES` or ``"full"`` (pads to the block's
        own max row nnz, rounded up to a power of two so that blocks share
        compiled samplers; the width argument is ignored).
      width: requested ELL width (floored to 1).
      block_rows: rows per block; a short last block is zero-padded.

    Returns ``(val, col, live_w, width, strategy)`` with ``val``/``col`` of
    shape ``[block_rows, width]`` and ``live_w`` int32[block_rows].
    """
    r0 = b * block_rows
    r1 = min(r0 + block_rows, csr.num_rows)
    if strat == "full":
        # first-W == all when W >= max nnz; a power-of-two W keeps the
        # number of distinct segment shapes (one compile each) to ~log2
        width = 1 << (int(row_nnz_host[r0:r1].max(initial=1)) - 1) \
            .bit_length()
    width = max(int(width), 1)
    if csr.nnz == 0 or r1 <= r0:
        v = jnp.zeros((block_rows, width), csr.val.dtype)
        c = jnp.zeros((block_rows, width), jnp.int32)
        live = jnp.zeros((block_rows,), jnp.int32)
    else:
        v, c, live = _sample_segment(csr.row_ptr, csr.col_ind, csr.val, r0,
                                     strat=strat, width=width, rows=r1 - r0,
                                     block_rows=block_rows)
    return v, c, live, width, strat


@functools.partial(jax.jit,
                   static_argnames=("strat", "width", "rows", "block_rows"))
def _sample_segment(row_ptr, col_ind, val, r0, *, strat: str, width: int,
                    rows: int, block_rows: int):
    """One compiled program per segment shape: the rows ``[r0, r0 +
    rows)`` sampled at ``width``, padded to ``block_rows``, with their
    live widths."""
    from repro.core.graph import ell_live_widths

    fn = sample_csr_to_ell_sfs if strat == "full" else STRATEGIES[strat]
    sub_ptr = jax.lax.dynamic_slice_in_dim(row_ptr, r0, rows + 1)
    v, c = fn(sub_ptr, col_ind, val, width)
    pad = ((0, block_rows - rows), (0, 0))
    v, c = jnp.pad(v, pad), jnp.pad(c, pad)
    return v, c, ell_live_widths(v, c)


def sample_csr_to_block_ell(csr, configs, block_rows: int):
    """Stitch a mixed-width :class:`~repro.core.graph.BlockELL` from a CSR.

    Args:
      csr: the source matrix.
      configs: sequence of ``(strategy, width)`` pairs, one per row block
        (``ceil(num_rows / block_rows)`` entries).  ``strategy`` is a key of
        :data:`STRATEGIES` or ``"full"``; for ``"full"`` the width argument
        is ignored and the block pads to its own max row nnz rounded up to
        a power of two (exact, no edge dropped).
      block_rows: rows per block.  The last block is padded with empty rows.

    Returns:
      ``BlockELL`` whose block ``b`` equals running ``STRATEGIES[s]`` on the
      sub-CSR of rows ``[b*block_rows, (b+1)*block_rows)`` with width
      ``configs[b][1]`` — each sampler sees the *global* ``col_ind``/``val``
      arrays through the sliced ``row_ptr``, so no per-block copy of the
      edge arrays is made.
    """
    from repro.core.graph import BlockELL

    num_rows = csr.num_rows
    num_blocks = max(-(-num_rows // block_rows), 1)
    if len(configs) != num_blocks:
        raise ValueError(
            f"expected {num_blocks} block configs for {num_rows} rows at "
            f"block_rows={block_rows}, got {len(configs)}")

    row_nnz_host = np.asarray(csr.row_ptr[1:]) - np.asarray(csr.row_ptr[:-1])
    # Segments are assembled on the host: a device concatenate of one
    # operand per block compiles anew for every block count and shape mix.
    vals, cols, lives, widths, strategies = [], [], [], [], []
    for b, (strat, width) in enumerate(configs):
        v, c, live, w, s = sample_block_segment(
            csr, row_nnz_host, b, strat, width, block_rows)
        lives.append(np.asarray(live))
        vals.append(np.asarray(v).reshape(-1))
        cols.append(np.asarray(c).reshape(-1))
        widths.append(w)
        strategies.append(s)

    # Trailing zero pad: lets the block kernel's fixed-size staging DMA
    # read past the last segment without a per-request jnp.pad copy
    # (serving hits run straight off this operand).
    from repro.kernels.gather import block_tail

    tail = block_tail(max(widths))
    vals.append(np.zeros(tail, vals[0].dtype))
    cols.append(np.zeros(tail, np.int32))
    live_w = np.concatenate(lives)
    bell = BlockELL(
        val=jnp.asarray(np.concatenate(vals)),
        col=jnp.asarray(np.concatenate(cols)),
        live_w=jnp.asarray(live_w), widths=tuple(widths),
        strategies=tuple(strategies), block_rows=block_rows,
        num_rows=num_rows, num_cols=csr.num_cols)
    if obs.enabled():
        # blocked-path twin of the sample() quality counters: edges the
        # stitched mixed-width operand kept vs. discarded, plus the slot
        # count the per-block widths allocated (tightness vs. nnz); the
        # live widths are the host's own
        kept = int(live_w[:num_rows].sum())
        obs.count("sampler.block_calls")
        obs.count("sampler.edges_kept", kept)
        obs.count("sampler.edges_dropped", max(int(csr.nnz) - kept, 0))
        obs.count("sampler.block_slots", bell.total_slots)
    return bell


def sampling_rate(row_ptr, sh_width: int) -> float:
    """Fraction of edges covered by AES sampling (unique offsets), used for
    the Fig. 5 CDF reproduction.  Host-side helper (numpy semantics).
    """
    import numpy as np

    row_ptr = np.asarray(row_ptr)
    row_nnz = row_ptr[1:] - row_ptr[:-1]
    total = int(row_nnz.sum())
    if total == 0:
        return 1.0
    strat = jax.device_get(get_sample_strategy(jnp.asarray(row_nnz), sh_width))
    off, valid = jax.device_get(
        slot_offsets(sh_width, SampleStrategy(*map(jnp.asarray, strat)), jnp.asarray(row_nnz))
    )
    covered = 0
    for r in range(len(row_nnz)):
        covered += len(np.unique(off[r][valid[r]]))
    return covered / total
