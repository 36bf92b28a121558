"""Graph containers: CSR (paper §2.2, Fig. 1), the fixed-width ELL layout
AES sampling produces, the mixed-width BlockELL layout the per-row-block
tuner stitches, plus the GNN normalizations the models need.

CSR uses the standard three arrays (row_ptr, col_ind, val).  AES-SpMM adopts
CSR directly ("eliminates overhead from additional format conversion"), and
the sampler emits fixed-width ELL — the TPU-regular layout (DESIGN.md §2).
``BlockELL`` generalizes ELL to one width per fixed-size row block so a
bimodal degree distribution pays a narrow width on its sparse tail and a
wide one only on its dense head (ROADMAP "per-row-block configs").
"""
from __future__ import annotations

import hashlib
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

#: Row granularity of the content-digest blocks the plan-cache fingerprint
#: is assembled from (``csr_block_digests``).  Fixed — independent of any
#: plan's ``block_rows`` knob — so the fingerprint of a CSR is a pure
#: function of its content, and an edge delta only dirties the digests of
#: the row blocks it touches (``repro.tuning.incremental``).
DIGEST_BLOCK_ROWS = 4096


class CSR(NamedTuple):
    """Compressed sparse row matrix.

    Invariants:
      * ``row_ptr`` is int32[num_rows + 1], non-decreasing, ``row_ptr[0] == 0``
        and ``row_ptr[-1] == nnz``;
      * ``col_ind`` is int32[nnz] with entries in ``[0, num_cols)``; entries
        of one row are stored contiguously (sorted per row by construction
        in :func:`csr_from_edges`, though no consumer requires sortedness);
      * ``val`` is f32[nnz], aligned with ``col_ind``.
    """

    row_ptr: jax.Array  # int32[rows + 1]
    col_ind: jax.Array  # int32[nnz]
    val: jax.Array      # f32[nnz]
    num_cols: int

    @property
    def num_rows(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def nnz(self) -> int:
        return self.col_ind.shape[0]

    def row_nnz(self) -> jax.Array:
        """Non-zeros per row: int32[num_rows]."""
        return (self.row_ptr[1:] - self.row_ptr[:-1]).astype(jnp.int32)


class ELL(NamedTuple):
    """Fixed-width sampled layout: row r's live entries sit in
    ``val[r, :], col[r, :]`` with dead slots zero-valued.

    Invariants:
      * live slots form a contiguous prefix of each row (every sampler
        fills slots ``s < live_w(r)`` and zeroes the rest);
      * the padding sentinel is ``val == 0`` *and* ``col == 0`` — a dead
        slot gathers row 0 of B but multiplies it by 0, so padding is an
        exact no-op in the SpMM accumulation;
      * ``width`` is the static shared-memory width W the sampler was run
        with (``min(row_nnz, W)`` slots are live per row).
    """

    val: jax.Array  # f32[rows, W]
    col: jax.Array  # int32[rows, W]
    num_cols: int

    @property
    def num_rows(self) -> int:
        return self.val.shape[0]

    @property
    def width(self) -> int:
        return self.val.shape[1]


class BlockELL(NamedTuple):
    """Mixed-width ELL: one (strategy, width) per fixed-size row block.

    The rows are partitioned into ``num_blocks = ceil(num_rows /
    block_rows)`` blocks of ``block_rows`` rows each (the last block is
    padded with empty rows up to ``block_rows`` so every block is uniform).
    Block ``b`` is an ordinary ELL segment of shape
    ``[block_rows, widths[b]]`` stored *flattened* row-major inside the
    shared 1-D ``val``/``col`` arrays; its slots start at
    ``slot_offsets()[b] = block_rows * sum(widths[:b])``.

    Invariants:
      * ``widths`` / ``strategies`` are static Python tuples of length
        ``num_blocks`` — widths are >= 1; strategies name entries of
        ``repro.core.sampling.STRATEGIES`` or ``"full"``;
      * the padding sentinel matches :class:`ELL`: dead slots carry
        ``val == 0`` and ``col == 0`` and live slots form a contiguous
        prefix of each row, of length ``live_w[row]``;
      * ``live_w`` is int32[num_blocks * block_rows] (padded rows included,
        with ``live_w == 0``); ``num_rows`` is the *logical* row count;
      * ``val``/``col`` may carry ``kernels.gather.block_tail(max_width)``
        zeroed elements past ``total_slots`` (the stitcher appends them)
        so the block kernel's fixed-size staging DMA can over-read safely
        without a per-call pad.
    """

    val: jax.Array              # f32[total_slots]  flattened block segments
    col: jax.Array              # int32[total_slots]
    live_w: jax.Array           # int32[num_blocks * block_rows]
    widths: tuple               # static int per block
    strategies: tuple           # static strategy name per block
    block_rows: int
    num_rows: int
    num_cols: int

    @property
    def num_blocks(self) -> int:
        return len(self.widths)

    @property
    def padded_rows(self) -> int:
        return self.num_blocks * self.block_rows

    @property
    def total_slots(self) -> int:
        return self.block_rows * sum(self.widths)

    @property
    def max_width(self) -> int:
        return max(self.widths) if self.widths else 1

    def slot_offsets(self) -> tuple:
        """Static slot offset of each block segment inside ``val``/``col``."""
        offs, acc = [], 0
        for w in self.widths:
            offs.append(acc)
            acc += self.block_rows * w
        return tuple(offs)

    def block_segment(self, b: int) -> tuple[jax.Array, jax.Array]:
        """Block ``b`` as 2-D ELL arrays ``(val[block_rows, widths[b]],
        col[block_rows, widths[b]])`` — a zero-copy reshape of the flat
        storage (offsets and widths are static)."""
        off = self.slot_offsets()[b]
        w = self.widths[b]
        n = self.block_rows * w
        return (self.val[off:off + n].reshape(self.block_rows, w),
                self.col[off:off + n].reshape(self.block_rows, w))

    def live_edges(self) -> int:
        """Total live slots over logical rows — the blocked analogue of the
        cost model's ``sum_r min(row_nnz_r, W)`` (edge-coverage numerator)."""
        return int(np.asarray(self.live_w)[:self.num_rows].sum())


def partition_width_buckets(widths, max_buckets: int = 3) -> tuple:
    """Partition BlockELL blocks into <= ``max_buckets`` width buckets.

    Pallas copy sizes are static, so a single launch over mixed-width blocks
    must DMA every row at ``max(widths)`` — narrow tail blocks pay the dense
    head's width.  Launching once per *bucket* instead lets each launch use
    its own static row-DMA width (the bucket's max).  This chooses the
    partition: group the distinct widths into at most ``max_buckets``
    contiguous (in sorted-width order) groups minimizing the total
    over-read, ``sum_b (bucket_width - widths[b])`` over blocks — exact DP,
    deterministic, O(#distinct_widths^2 * max_buckets).

    Args:
      widths: per-block ELL widths (``BlockELL.widths``).
      max_buckets: launch budget (2-3 captures most of the win; 1 recovers
        the single-launch max-width behavior).

    Returns a tuple of ``(bucket_width, block_ids)`` pairs, ascending by
    width, where ``bucket_width = max(widths[i] for i in block_ids)`` and
    ``block_ids`` is an ascending tuple.  The ``block_ids`` concatenated
    over all buckets are a permutation of ``range(len(widths))`` — no block
    dropped or duplicated (property-tested).
    """
    widths = tuple(int(w) for w in widths)
    if not widths:
        return ()
    max_buckets = max(int(max_buckets), 1)
    uniq = sorted(set(widths))
    counts = [sum(1 for w in widths if w == u) for u in uniq]
    m = len(uniq)
    k = min(max_buckets, m)

    # cost[i][j]: over-read of one bucket covering uniq[i..j] (width uniq[j])
    cost = [[0] * m for _ in range(m)]
    for i in range(m):
        for j in range(i, m):
            cost[i][j] = sum(counts[t] * (uniq[j] - uniq[t])
                             for t in range(i, j + 1))
    # best[i][g]: min cost splitting uniq[i:] into exactly g buckets
    INF = float("inf")
    best = [[INF] * (k + 1) for _ in range(m + 1)]
    cut = [[m] * (k + 1) for _ in range(m + 1)]
    best[m][0] = 0.0
    for i in range(m - 1, -1, -1):
        for g in range(1, k + 1):
            for j in range(i, m):
                c = cost[i][j] + best[j + 1][g - 1]
                if c < best[i][g]:
                    best[i][g], cut[i][g] = c, j
    g = min(range(1, k + 1), key=lambda gg: (best[0][gg], gg))
    bounds, i = [], 0
    while i < m:
        j = cut[i][g]
        bounds.append(uniq[j])
        i, g = j + 1, g - 1

    buckets = []
    lo = -1
    for hi in bounds:
        ids = tuple(b for b, w in enumerate(widths) if lo < w <= hi)
        if ids:
            buckets.append((max(widths[b] for b in ids), ids))
        lo = hi
    return tuple(buckets)


def ell_live_widths(val: jax.Array, col: jax.Array) -> jax.Array:
    """Per-row live-prefix lengths of an ELL segment, decoded from the
    padding sentinel (dead slot == ``val == 0 and col == 0``; live slots
    are a contiguous prefix — the invariant shared by ELL and BlockELL).

    Args:
      val / col: one fixed-width segment, ``[rows, W]``.

    Returns int32[rows]: ``1 +`` the last live slot index (0 for all-dead
    rows).  The single source of truth for sentinel decoding — keep kernel
    wrappers and stitchers on this helper so a future sentinel change has
    one home.
    """
    width = val.shape[1]
    mask = (val != 0) | (col != 0)
    pos = jnp.arange(1, width + 1, dtype=jnp.int32)[None, :]
    return jnp.max(jnp.where(mask, pos, 0), axis=1).astype(jnp.int32)


def csr_from_edges(src: np.ndarray, dst: np.ndarray, num_nodes: int,
                   val: np.ndarray | None = None) -> CSR:
    """Build CSR of the adjacency A[dst, src] (messages flow src -> dst,
    aggregation is a row-gather over in-neighbors)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    order = np.lexsort((src, dst))
    src, dst = src[order], dst[order]
    v = np.ones(len(src), np.float32) if val is None else np.asarray(val, np.float32)[order]
    counts = np.bincount(dst, minlength=num_nodes)
    row_ptr = np.zeros(num_nodes + 1, np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    return CSR(jnp.asarray(row_ptr), jnp.asarray(src.astype(np.int32)),
               jnp.asarray(v), num_cols=num_nodes)


def add_self_loops(csr: CSR) -> CSR:
    """A + I (GCN convention) — host-side rebuild."""
    rp = np.asarray(csr.row_ptr)
    ci = np.asarray(csr.col_ind)
    v = np.asarray(csr.val)
    n = csr.num_rows
    dst = np.repeat(np.arange(n), rp[1:] - rp[:-1])
    src = np.concatenate([ci, np.arange(n)])
    dst = np.concatenate([dst, np.arange(n)])
    val = np.concatenate([v, np.ones(n, np.float32)])
    return csr_from_edges(src, dst, n, val)


def gcn_normalize(csr: CSR, add_loops: bool = True) -> CSR:
    """Symmetric normalization D^-1/2 (A + I) D^-1/2 (Kipf & Welling)."""
    if add_loops:
        csr = add_self_loops(csr)
    rp = np.asarray(csr.row_ptr)
    ci = np.asarray(csr.col_ind)
    deg_in = (rp[1:] - rp[:-1]).astype(np.float64)          # row degree
    deg_out = np.bincount(ci, minlength=csr.num_rows).astype(np.float64)
    d_in = 1.0 / np.sqrt(np.maximum(deg_in, 1.0))
    d_out = 1.0 / np.sqrt(np.maximum(deg_out, 1.0))
    rows = np.repeat(np.arange(csr.num_rows), rp[1:] - rp[:-1])
    val = (np.asarray(csr.val) * d_in[rows] * d_out[ci]).astype(np.float32)
    return CSR(csr.row_ptr, csr.col_ind, jnp.asarray(val), csr.num_cols)


def mean_normalize(csr: CSR) -> CSR:
    """Row-mean normalization D^-1 A (GraphSAGE mean aggregator)."""
    rp = np.asarray(csr.row_ptr)
    deg = (rp[1:] - rp[:-1]).astype(np.float64)
    rows = np.repeat(np.arange(csr.num_rows), rp[1:] - rp[:-1])
    val = (np.asarray(csr.val) / np.maximum(deg, 1.0)[rows]).astype(np.float32)
    return CSR(csr.row_ptr, csr.col_ind, jnp.asarray(val), csr.num_cols)


def permute_csr_rows(csr: CSR, perm) -> CSR:
    """Reorder a CSR's rows by ``perm`` (row ``r`` of the result is row
    ``perm[r]`` of the input).  Columns are untouched — the dense operand
    of an SpMM over the permuted matrix needs no reindexing, only the
    *output* rows come back permuted.

    Host-side numpy rebuild: one vectorized gather over the edge arrays,
    one device crossing for the result.  Per-row edge order (and therefore
    SpMM accumulation order) is preserved, so row ``r`` of the permuted
    matrix is byte-identical to row ``perm[r]`` of the input.
    """
    perm = np.asarray(perm, np.int64)
    rp = np.asarray(csr.row_ptr, np.int64)
    nnz = rp[1:] - rp[:-1]
    counts = nnz[perm]
    new_rp = np.zeros(csr.num_rows + 1, np.int64)
    np.cumsum(counts, out=new_rp[1:])
    # edge i of the output copies from its source row's slice: offset
    # within the row is (i - new_row_start), shifted to the old row start
    idx = (np.repeat(rp[perm] - new_rp[:-1], counts)
           + np.arange(int(new_rp[-1]), dtype=np.int64))
    return CSR(jnp.asarray(new_rp.astype(np.int32)),
               jnp.asarray(np.asarray(csr.col_ind)[idx]),
               jnp.asarray(np.asarray(csr.val)[idx]),
               num_cols=csr.num_cols)


def degree_sort_permutation(csr: CSR):
    """Stable nnz-descending row permutation — the load-balancing layout
    trick (MindSpore CSR / ES-SpMM lineage): sorting rows by degree before
    blocking packs hub rows into a few wide blocks and leaves the sparse
    tail in narrow ones, so per-block ELL widths tighten and the width
    buckets collapse.

    Returns ``(perm, inv_perm, permuted_csr)`` where ``permuted_csr ==
    permute_csr_rows(csr, perm)`` (columns untouched), ``perm[p]`` is the
    natural row id at permuted position ``p``, and ``inv_perm[r]`` is the
    permuted position of natural row ``r`` — so an output computed in
    permuted order is restored by ``out[inv_perm]``.  The sort is stable
    (equal-degree rows keep their natural order), making the permutation a
    pure function of the degree sequence.
    """
    rp = np.asarray(csr.row_ptr, np.int64)
    nnz = rp[1:] - rp[:-1]
    perm = np.argsort(-nnz, kind="stable").astype(np.int64)
    inv_perm = np.empty_like(perm)
    inv_perm[perm] = np.arange(perm.size, dtype=np.int64)
    return perm, inv_perm, permute_csr_rows(csr, perm)


def csr_to_dense(csr: CSR) -> jax.Array:
    """Densify: f32[num_rows, num_cols] with duplicate edges accumulated —
    the exact reference the sampled kernels are tested against."""
    rows = jnp.repeat(jnp.arange(csr.num_rows), csr.row_nnz(),
                      total_repeat_length=csr.nnz)
    dense = jnp.zeros((csr.num_rows, csr.num_cols), csr.val.dtype)
    return dense.at[rows, csr.col_ind].add(csr.val)


def pad_csr_to_ell(csr: CSR, width: int | None = None) -> ELL:
    """No-sampling ELL: every row padded to max row_nnz (GE-SpMM-role
    baseline keeps all edges; only the layout changes).

    Args:
      csr: source matrix.
      width: override the ELL width (default: the graph's max row nnz —
        narrower values truncate rows, first-W).

    Returns an exact ``ELL`` when ``width >= max(row_nnz)``.
    """
    # width floor of 1 keeps the ELL two-dimensional on an all-empty graph
    # (a [rows, 0] operand breaks downstream kernel tiling)
    nnz = np.asarray(csr.row_nnz())
    w = max(int(nnz.max(initial=0)), 1) if width is None else width
    from .sampling import sample_csr_to_ell_sfs  # first-W == all when w >= max nnz

    val, col = sample_csr_to_ell_sfs(csr.row_ptr, csr.col_ind, csr.val, w)
    return ELL(val, col, csr.num_cols)


def num_digest_blocks(num_rows: int,
                      digest_rows: int = DIGEST_BLOCK_ROWS) -> int:
    """Digest-block count for a row count (>= 1 even for an empty graph, so
    every CSR — including 0-row ones — has at least one content digest)."""
    return max(-(-int(num_rows) // int(digest_rows)), 1)


# Identity-keyed digest memo.  CSR arrays are treated as immutable
# throughout the library, so a digest computed once for a given
# (row_ptr, col_ind, val) triple stays valid for the objects' lifetime.
# Entries evict when the backing col_ind array is garbage collected
# (weakref.finalize); the size cap is a backstop for array types without
# weakref support.  Only digests *computed from the data* are ever stored
# — nothing seeds this cache — so differential digest checks stay
# meaningful.
_DIGEST_MEMO: dict = {}
_DIGEST_MEMO_CAP = 512


def _digest_memo(csr: CSR) -> dict:
    key = (id(csr.row_ptr), id(csr.col_ind), id(csr.val))
    entry = _DIGEST_MEMO.get(key)
    if entry is None:
        if len(_DIGEST_MEMO) >= _DIGEST_MEMO_CAP:
            _DIGEST_MEMO.clear()
        entry = _DIGEST_MEMO[key] = {}
        try:
            weakref.finalize(csr.col_ind, _DIGEST_MEMO.pop, key, None)
        except TypeError:  # pragma: no cover - non-weakrefable array type
            pass
    return entry


def csr_block_digests(csr: CSR, digest_rows: int = DIGEST_BLOCK_ROWS,
                      blocks=None) -> list:
    """Content digests of fixed-granularity row blocks of a CSR.

    Digest block ``b`` covers rows ``[b * digest_rows, (b+1) * digest_rows)``
    and hashes the block's *locally normalized* row pointers
    (``row_ptr[r0:r1+1] - row_ptr[r0]``) plus its ``col_ind``/``val`` slices.
    Normalizing makes each digest independent of how many edges precede the
    block, so an edge delta in block 3 leaves blocks 0–2 and 4+ digests
    valid even though their absolute ``row_ptr`` offsets shifted — the
    property ``repro.tuning.incremental`` relies on to maintain the plan
    fingerprint without re-hashing the full CSR.

    Args:
      csr: source matrix.
      digest_rows: block granularity.  Leave at the default — the plan-cache
        fingerprint is defined over :data:`DIGEST_BLOCK_ROWS` blocks.
      blocks: optional iterable of block ids to digest (default: all
        ``num_digest_blocks`` blocks).  Used by the delta path to re-digest
        only touched blocks.

    Returns a list of 32-hex-char digests aligned with ``blocks``.

    Digests are memoized per array-identity of the CSR's backing buffers
    (the library never mutates them in place), so re-digesting blocks of a
    CSR object that was already tuned or patched is free — this is what
    keeps ``apply_edge_updates``'s wrong-graph guard off the patch path's
    critical cost in steady-state serving.
    """
    n = csr.num_rows
    if blocks is None:
        blocks = range(num_digest_blocks(n, digest_rows))
    blocks = [int(b) for b in blocks]
    memo = _digest_memo(csr)
    todo = [b for b in blocks if (digest_rows, b) not in memo]
    if todo:
        rp = np.asarray(csr.row_ptr, np.int64)
        ci = np.ascontiguousarray(np.asarray(csr.col_ind))
        v = np.ascontiguousarray(np.asarray(csr.val))
        for b in todo:
            r0 = min(b * digest_rows, n)
            r1 = min(r0 + digest_rows, n)
            lo, hi = int(rp[r0]), int(rp[r1])
            h = hashlib.blake2b(digest_size=16)
            h.update(np.ascontiguousarray(rp[r0:r1 + 1] - rp[r0]).tobytes())
            h.update(ci[lo:hi].tobytes())
            h.update(v[lo:hi].tobytes())
            memo[(digest_rows, b)] = h.hexdigest()
    return [memo[(digest_rows, b)] for b in blocks]


def combine_block_digests(digests, num_rows: int, num_cols: int,
                          digest_rows: int = DIGEST_BLOCK_ROWS) -> str:
    """Fold per-block digests into one CSR content fingerprint.

    ``combine(csr_block_digests(csr), csr.num_rows, csr.num_cols)`` equals
    :func:`repro.tuning.features.fingerprint` — the plan-cache key — by
    definition, so a plan patched block-by-block lands on exactly the key a
    cold tune of the same graph would compute.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(np.asarray([num_rows, num_cols, digest_rows], np.int64).tobytes())
    for d in digests:
        h.update(bytes.fromhex(d))
    return h.hexdigest()


def _parse_deltas(entries, what: str):
    """Normalize a delta list to (rows, cols, vals) int64/int64/f32 arrays.

    Accepts a sequence of ``(row, col)`` or ``(row, col, val)`` tuples (or
    an equivalent 2-D array).  Missing vals default to 1.0.
    """
    entries = np.asarray(list(entries), np.float64)
    if entries.size == 0:
        z = np.zeros(0, np.int64)
        return z, z, np.zeros(0, np.float32)
    if entries.ndim != 2 or entries.shape[1] not in (2, 3):
        raise ValueError(f"{what} must be (row, col[, val]) tuples, "
                         f"got shape {entries.shape}")
    rows = entries[:, 0].astype(np.int64)
    cols = entries[:, 1].astype(np.int64)
    if not (np.all(rows == entries[:, 0]) and np.all(cols == entries[:, 1])):
        raise ValueError(f"{what} rows/cols must be integers")
    vals = (entries[:, 2].astype(np.float32) if entries.shape[1] == 3
            else np.ones(len(rows), np.float32))
    return rows, cols, vals


def apply_csr_deltas(csr: CSR, additions=(), deletions=()):
    """Apply edge insertions and deletions to a CSR, tracking touched rows.

    The workhorse of the incremental plan-maintenance path: deletions are
    applied first, then additions.  The node set is fixed — deltas must
    reference existing row/col ids (graph growth is a re-partition, not a
    patch).  Strictness is deliberate: every delta must change the graph,
    so a patched plan's provenance is exact.

    Args:
      csr: source matrix.
      additions: ``(row, col)`` or ``(row, col, val)`` tuples; ``val``
        defaults to 1.0.  Adding a pair still present after deletions, a
        pair listed twice, or an out-of-range id raises ``ValueError``.
      deletions: ``(row, col)`` tuples.  A deletion removes *every* stored
        instance of the pair; deleting an absent or repeated pair raises
        ``ValueError``.

    Returns ``(new_csr, touched_rows)`` where ``touched_rows`` is a sorted
    unique int64 array.  Untouched rows keep byte-identical
    ``col_ind``/``val`` slices (their :func:`csr_block_digests` stay valid);
    touched rows are re-sorted by column.
    """
    add_r, add_c, add_v = _parse_deltas(additions, "additions")
    del_r, del_c, _ = _parse_deltas(deletions, "deletions")
    if add_r.size == 0 and del_r.size == 0:
        return csr, np.zeros(0, np.int64)

    n, m = csr.num_rows, csr.num_cols
    for what, r, c in (("additions", add_r, add_c),
                       ("deletions", del_r, del_c)):
        if r.size and (r.min() < 0 or r.max() >= n):
            raise ValueError(f"{what} row out of range [0, {n})")
        if c.size and (c.min() < 0 or c.max() >= m):
            raise ValueError(f"{what} col out of range [0, {m})")

    rp = np.asarray(csr.row_ptr, np.int64)
    ci = np.asarray(csr.col_ind, np.int64)
    v = np.asarray(csr.val, np.float32)
    edge_rows = np.repeat(np.arange(n, dtype=np.int64), rp[1:] - rp[:-1])

    touched = np.unique(np.concatenate([del_r, add_r]))
    touched_mask = np.zeros(n, bool)
    touched_mask[touched] = True
    edge_touched = touched_mask[edge_rows]

    # Every membership check below involves touched rows only, so the key
    # arithmetic stays O(touched edges) — a full-graph ``np.isin`` here
    # would dominate small-delta patches.
    tidx = np.flatnonzero(edge_touched)
    tkeys = edge_rows[tidx] * m + ci[tidx]
    # Rows are column-sorted in every CSR this module builds, making
    # tkeys already ascending — hub-heavy deltas touch most of the edge
    # mass, so skipping the re-sort (and the lexsort below) matters.
    presorted = tkeys.size == 0 or not np.any(tkeys[1:] < tkeys[:-1])
    stkeys = tkeys if presorted else np.sort(tkeys)

    def _member(sorted_keys, query):
        pos = np.searchsorted(sorted_keys, query)
        hit = pos < sorted_keys.size
        hit[hit] &= sorted_keys[pos[hit]] == query[hit]
        return hit

    del_keys = del_r * m + del_c
    if np.unique(del_keys).size != del_keys.size:
        raise ValueError("duplicate (row, col) pair in deletions")
    missing = ~_member(stkeys, del_keys)
    if missing.any():
        i = int(np.flatnonzero(missing)[0])
        raise ValueError(f"deletion ({del_r[i]}, {del_c[i]}) not present")
    keep = np.ones(len(edge_rows), bool)
    keep[tidx] = ~_member(np.sort(del_keys), tkeys)

    add_keys = add_r * m + add_c
    if np.unique(add_keys).size != add_keys.size:
        raise ValueError("duplicate (row, col) pair in additions")
    surv_keys = tkeys[keep[tidx]]          # order-preserving mask
    if not presorted:
        surv_keys = np.sort(surv_keys)
    clash = _member(surv_keys, add_keys)
    if clash.any():
        i = int(np.flatnonzero(clash)[0])
        raise ValueError(f"addition ({add_r[i]}, {add_c[i]}) already present")

    # surviving edges of touched rows + additions, re-sorted by (row, col)
    sel = edge_touched & keep
    sb_r, sb_c, sb_v = edge_rows[sel], ci[sel], v[sel]
    aorder = np.lexsort((add_c, add_r))
    sa_r, sa_c, sa_v = add_r[aorder], add_c[aorder], add_v[aorder]
    if presorted:
        # two-way merge of the (already sorted) survivors with the sorted
        # additions — no equal keys across the two (clash check above)
        ak = sa_r * m + sa_c
        nb, na = surv_keys.size, ak.size
        pr = np.empty(nb + na, np.int64)
        pc = np.empty(nb + na, np.int64)
        pv = np.empty(nb + na, np.float32)
        bpos = np.arange(nb) + np.searchsorted(ak, surv_keys)
        apos = np.searchsorted(surv_keys, ak) + np.arange(na)
        pr[bpos], pc[bpos], pv[bpos] = sb_r, sb_c, sb_v
        pr[apos], pc[apos], pv[apos] = sa_r, sa_c, sa_v
    else:
        pr = np.concatenate([sb_r, sa_r])
        pc = np.concatenate([sb_c, sa_c])
        pv = np.concatenate([sb_v, sa_v])
        order = np.lexsort((pc, pr))
        pr, pc, pv = pr[order], pc[order], pv[order]

    old_cnt = rp[1:] - rp[:-1]
    new_cnt = (old_cnt - np.bincount(edge_rows[~keep], minlength=n)
               + np.bincount(add_r, minlength=n))
    new_rp = np.zeros(n + 1, np.int64)
    np.cumsum(new_cnt, out=new_rp[1:])
    nnz_new = int(new_rp[-1])
    new_ci = np.empty(nnz_new, np.int64)
    new_v = np.empty(nnz_new, np.float32)

    # untouched edges land at their original within-row offsets
    un = np.flatnonzero(~edge_touched)
    dest = new_rp[edge_rows[un]] + (un - rp[edge_rows[un]])
    new_ci[dest] = ci[un]
    new_v[dest] = v[un]

    # touched rows: contiguous sorted groups at their new row starts
    pstart = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(pr, minlength=n), out=pstart[1:])
    dest = new_rp[pr] + (np.arange(len(pr), dtype=np.int64) - pstart[pr])
    new_ci[dest] = pc
    new_v[dest] = pv

    out = CSR(jnp.asarray(new_rp.astype(np.int32)),
              jnp.asarray(new_ci.astype(np.int32)),
              jnp.asarray(new_v), num_cols=m)
    return out, touched
