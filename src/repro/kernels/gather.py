"""The gather-accumulate core shared by every SpMM kernel in this package,
and the one module that knows the layout of the dense operand B.

Each sampled edge ``(val, col)`` of a row pulls its row of the dense
operand B out of HBM with its own DMA, double-buffered so the copy of
edge k+1 overlaps the FMA of edge k (:func:`gather_accumulate`), or with
a ring of DMAs in flight (:func:`gather_accumulate_ring`).  Three Mosaic
rules shape it:

  * a DMA address and a loop bound are scalars, and scalars live in
    **SMEM** — every edge list a kernel reads sits in an SMEM block or an
    SMEM scratch, and SMEM is small (:data:`SMEM_BUDGET`);
  * a DMA out of a tiled HBM array may cut a single row only out of an
    array one lane tile (128 words) wide, so B reaches the kernels as a
    stack of such arrays, ``[tiles, nodes, 128]`` (:class:`TiledFeatures`),
    and one strided DMA fetches a row from several of them; a 1-D array
    is read in windows aligned to its 1024-element tiling
    (:func:`stage_flat`);
  * rows are accumulated as ``[1, 128]`` pieces and assembled into
    sublane-aligned ``[group, pieces * 128]`` tiles before they are stored
    (:func:`for_row_groups`), so no kernel stores one row at a dynamic
    sublane.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import weakref

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dequant import dequant_epilogue

SUBLANES = 8
LANES = 128
# HBM tiling of a 1-D 32-bit array: a DMA out of one starts and ends on a
# multiple of this many elements.
FLAT_TILE = 1024
# SMEM a kernel's blocks and scratch may take: a v5e core's 1 MiB, less
# what Mosaic keeps for itself (2.1-3.2 KiB in the v5e compiles of
# tests/test_tpu_compile.py).
SMEM_BUDGET = (1 << 20) - (4 << 10)
# SMEM words one staging window may take.
STAGE_WORDS = 1 << 16


def smem_words(shape) -> int:
    """32-bit words an SMEM array of ``shape`` occupies: a 1-D array pads
    to whole 1024-word tiles, a 2-D one each row to whole 128-word lanes."""
    if len(shape) == 1:
        return pl.cdiv(shape[0], FLAT_TILE) * FLAT_TILE
    *lead, last = shape
    return math.prod(lead) * pl.cdiv(last, LANES) * LANES


def edge_tile_smem_bytes(block_r: int, width: int) -> int:
    """SMEM of a row tile's edge list as the ELL kernels stage it: the
    ``[block_r, width]`` val and col blocks and the ``[1, block_r]`` live
    widths, each double-buffered by the pipeline."""
    return 4 * 2 * (2 * smem_words((block_r, width))
                    + smem_words((1, block_r)))


def check_smem(nbytes: int, what: str) -> None:
    """Refuse a launch whose SMEM buffers exceed :data:`SMEM_BUDGET`."""
    if nbytes > SMEM_BUDGET:
        raise ValueError(
            f"{what} needs {nbytes} bytes of SMEM, over the budget of "
            f"{SMEM_BUDGET}; use a narrower ELL width or the jax backend")


def lane_pack(dtype, feat: int, width: int) -> int:
    """Features one 32-bit HBM word carries in a ``width``-word feature
    tile of a ``feat``-wide operand: 1 for a float operand; for a
    quantized one as many as fit (4 for uint8, 2 for uint16), fewer when
    ``feat`` would leave a whole bit field of every word empty."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.integer):
        return 1
    pack = 4 // dtype.itemsize
    while pack > 1 and (pack // 2) * width >= feat:
        pack //= 2
    return pack


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["tiles"],
                   meta_fields=["num_features", "pack", "bits"])
@dataclasses.dataclass(frozen=True)
class TiledFeatures:
    """The dense operand B as the kernels read it: ``[F_p / (pack *
    width), nodes, width]``, a stack of HBM arrays one lane tile wide
    (``width`` is 128 on the chip), F padded with zero features to whole
    tiles.

    A float operand keeps its dtype (``bits`` None).  A quantized one
    (uint8/uint16, ``bits`` wide) goes into int32 words, ``pack`` features
    each: Mosaic cannot cut one row out of the sub-word tiling of a narrow
    integer array, and a row DMA moves at least 128 words.  Word ``j`` of
    a tile holds the tile's features ``j, width + j, ...``, so
    :func:`unpack_words` turns a row into ``[pack, width]`` lane-aligned
    pieces with shifts and masks alone.  A row DMA moves ``4 * width``
    bytes whatever ``pack`` is, so a uint8 operand moves fewer bytes than
    a float one only from F > 128.
    """

    tiles: jax.Array
    num_features: int           # F before padding
    pack: int
    bits: int | None

    @property
    def width(self) -> int:
        return self.tiles.shape[2]

    @property
    def padded_features(self) -> int:
        """F_p: features the kernels compute, padding included."""
        return self.tiles.shape[0] * self.pack * self.width


@functools.partial(jax.jit, static_argnames=("width",))
def tile_features(b, width: int = LANES) -> TiledFeatures:
    """``[nodes, F] -> TiledFeatures``: one pass over B."""
    n, f = b.shape
    pack = lane_pack(b.dtype, f, width)
    fp = pl.cdiv(f, pack * width) * pack * width
    if fp != f:
        b = jnp.pad(b, ((0, 0), (0, fp - f)))
    x = b.reshape(n, fp // (pack * width), pack, width)
    bits = None
    if jnp.issubdtype(b.dtype, jnp.integer):
        bits = 8 * b.dtype.itemsize
        shift = (bits * jnp.arange(pack, dtype=jnp.uint32))[None, None, :,
                                                             None]
        words = jnp.sum(x.astype(jnp.uint32) << shift, axis=2,
                        dtype=jnp.uint32)    # disjoint bit fields: sum == or
        x = jax.lax.bitcast_convert_type(words, jnp.int32)
    else:
        x = x[:, :, 0, :]
    return TiledFeatures(x.transpose(1, 0, 2), f, pack, bits)


# Tiled layouts of the live jax.Arrays they were built from, so an
# operand that is served many times (graph features, a plan's quantized
# features) is re-laid out once, not on every SpMM call.
_TILED: dict = {}


def tiled(b, width: int = LANES) -> TiledFeatures:
    """``b`` in the kernels' layout, built at most once per immutable
    array (traced values and host arrays are laid out on every call)."""
    if isinstance(b, TiledFeatures):
        return b
    if not isinstance(b, jax.Array) or isinstance(b, jax.core.Tracer):
        return tile_features(jnp.asarray(b), width)
    key = (id(b), width)
    hit = _TILED.get(key)
    if hit is not None and hit[0]() is b:
        return hit[1]
    t = tile_features(b, width)
    _TILED[key] = (weakref.ref(b, lambda _: _TILED.pop(key, None)), t)
    return t


def unpack_words(row, pack: int, bits: int):
    """int32[1, width] words -> int32[pack, width]: piece ``k`` is the
    ``k``-th ``bits``-wide field of every word (see :class:`TiledFeatures`)."""
    shape = (pack, row.shape[-1])
    shift = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * bits
    words = jnp.broadcast_to(row, shape)
    return jax.lax.shift_right_logical(words, shift) & ((1 << bits) - 1)


def vmem_limit(nbytes: int) -> int:
    """Scoped-VMEM limit for a kernel whose pipelined blocks and scratch
    take ``nbytes``: the default 16 MiB, raised with 4 MiB of headroom for
    Mosaic's own scratch when the buffers need more."""
    return max(16 << 20, nbytes + (4 << 20))


def row_group(num_rows: int) -> int:
    """Rows per output tile of :func:`for_row_groups`: the largest divisor
    of ``num_rows`` up to one sublane tile."""
    return math.gcd(num_rows, SUBLANES)


def stage_rows(group: int, max_w: int) -> int:
    """Rows of ``max_w`` slots one staging window holds: a whole row group
    while its window stays within ``STAGE_WORDS`` of SMEM, else one row."""
    return group if flat_window(group * max_w) <= STAGE_WORDS else 1


def block_tail(max_w: int) -> int:
    """Zeroed elements a BlockELL's flat ``val``/``col`` carry past their
    last segment, so the block kernel's staging window (one row group of
    ``max_w`` slots) never reads out of bounds."""
    return flat_window(SUBLANES * max_w)


def flat_window(n: int) -> int:
    """Static size of an aligned window that holds any ``n`` consecutive
    elements of a 1-D HBM array.  An array read through :func:`stage_flat`
    carries this many trailing pad elements past its last real entry."""
    return pl.cdiv(n, FLAT_TILE) * FLAT_TILE + FLAT_TILE


def stage_flat(pairs, sems, start, n: int):
    """Copy the aligned window holding ``[start, start + n)`` of each 1-D
    HBM ref into its SMEM stage; returns the offset of ``start`` in it.

    pairs: ``((hbm_ref, smem_stage[flat_window(n)]), ...)``, copied
    concurrently on one semaphore of ``sems`` each.
    """
    base = pl.multiple_of((start // FLAT_TILE) * FLAT_TILE, FLAT_TILE)
    copies = [pltpu.make_async_copy(ref.at[pl.ds(base, flat_window(n))],
                                    stage, sems.at[i])
              for i, (ref, stage) in enumerate(pairs)]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    return start - base


def _row_copy(b_ref, bsc, sem, col_at):
    """``(k, slot) ->`` the DMA of edge ``k``'s row, every tile of b_ref
    in one strided copy, into landing slot ``slot`` on its semaphore."""
    def copy(k, slot):
        return pltpu.make_async_copy(b_ref.at[:, pl.ds(col_at(k), 1), :],
                                     bsc.at[slot], sem.at[slot])
    return copy


def _land(bsc, slot, v, acc, *, pack, bits, scale, x_min):
    """``acc + v * row`` for the row landed in ``slot``, tile by tile,
    dequantized with Eq. 2 first when ``bits`` is set."""
    out = []
    for t in range(bsc.shape[1]):
        row = bsc[slot, t]
        if bits is not None:
            row = dequant_epilogue(unpack_words(row, pack, bits), scale,
                                   x_min)
        out.append(acc[t] + v * row.astype(jnp.float32))
    return tuple(out)


def _edge_loop(bsc, live, body, *, pack, bits):
    """``body(k, acc)`` for ``k < live`` from zero sums, one
    ``[pack, width]`` sum (``[1, width]`` for a float operand) a tile;
    returns the sums as ``[1, width]`` pieces in feature order."""
    _, tiles, _, width = bsc.shape
    rows = pack if bits is not None else 1
    acc = jax.lax.fori_loop(
        0, live, body,
        tuple(jnp.zeros((rows, width), jnp.float32) for _ in range(tiles)))
    return [a[i:i + 1, :] for a in acc for i in range(rows)]


def gather_accumulate(b_ref, bsc, sem, live, col_at, val_at, *,
                      pack: int = 1, bits=None, scale=1.0, x_min=0.0):
    """``sum_{k < live} val_at(k) * B[col_at(k), :]`` as ``tiles * pack``
    f32[1, width] pieces, in feature order.

    Args:
      b_ref: the dense operand's tiles this row reads, [tiles, nodes,
        width] in HBM (:attr:`TiledFeatures.tiles`): f32, or (``bits`` set)
        ``bits``-wide quantized features packed ``pack`` to an int32 word,
        dequantized with Eq. 2 (``q * scale + x_min``) as each row lands.
      bsc: VMEM scratch ``[2, tiles, 1, width]`` of b_ref's dtype, the
        double-buffered row landing zone (one strided DMA fills a slot).
      sem: DMA semaphores ``[2]``.
      live: traced scalar edge count of this row.
      col_at / val_at: ``k -> scalar`` readers of the row's edge list (SMEM).
    """
    copy = _row_copy(b_ref, bsc, sem, col_at)

    @pl.when(live > 0)
    def _():
        copy(0, 0).start()

    def body(k, acc):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < live)
        def _():
            copy(k + 1, 1 - slot).start()

        copy(k, slot).wait()
        return _land(bsc, slot, val_at(k), acc, pack=pack, bits=bits,
                     scale=scale, x_min=x_min)

    return _edge_loop(bsc, live, body, pack=pack, bits=bits)


def gather_accumulate_ring(b_ref, bsc, sem, live, col_at, val_at, *,
                           pack: int = 1, bits=None, scale=1.0, x_min=0.0):
    """:func:`gather_accumulate` with up to ``depth`` row DMAs in flight:
    ``bsc`` is ``[depth, tiles, 1, width]`` and ``sem`` ``[depth]``.

    Edge ``k`` lands in slot ``k % depth``.  The first ``min(live,
    depth)`` copies start before the loop; iteration ``k`` waits for its
    slot, accumulates it, then starts copy ``k + depth`` into the slot it
    has just read.  The sums run in the same order ``k = 0 .. live - 1``
    as :func:`gather_accumulate`, so the two agree bit for bit.
    """
    depth = bsc.shape[0]
    copy = _row_copy(b_ref, bsc, sem, col_at)

    for s in range(depth):
        @pl.when(s < live)
        def _():
            copy(s, s).start()

    def body(k, acc):
        slot = jax.lax.rem(k, depth)
        copy(k, slot).wait()
        acc = _land(bsc, slot, val_at(k), acc, pack=pack, bits=bits,
                    scale=scale, x_min=x_min)

        @pl.when(k + depth < live)
        def _():
            copy(k + depth, slot).start()

        return acc

    return _edge_loop(bsc, live, body, pack=pack, bits=bits)


def for_row_groups(num_rows: int, row_acc, store_group):
    """Run ``row_acc(base, r)`` (a list of f32[1, width] pieces) for row
    ``base + r`` of every group and hand each group to
    ``store_group(base, tile)``, with ``tile`` f32[group, pieces * width]
    (the pieces side by side).

    The group is the largest divisor of ``num_rows`` up to 8 rows, so a
    tile store starts at a multiple of its own height.  ``r`` is a static
    Python int: ``r == 0`` marks the first row of a group.
    """
    g = row_group(num_rows)

    def group(gi, carry):
        base = pl.multiple_of(gi * g, g)
        tiles = None
        for r in range(g):
            pieces = row_acc(base, r)
            shape = (g, pieces[0].shape[-1])
            rid = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            if tiles is None:
                tiles = [jnp.zeros(shape, jnp.float32)] * len(pieces)
            tiles = [jnp.where(rid == r, p, t) for p, t in zip(pieces, tiles)]
        store_group(base, tiles[0] if len(tiles) == 1
                    else jnp.concatenate(tiles, axis=1))
        return carry

    jax.lax.fori_loop(0, num_rows // g, group, 0)
