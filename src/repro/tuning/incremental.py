"""Incremental plan maintenance: patch a cached ``BlockedPlan`` in place
of a whole-graph re-tune when the graph mutates under live traffic.

Production graphs gain and lose edges constantly; re-keying the plan cache
by a full-CSR fingerprint would turn every edge insert into a cold tune —
the exact preprocessing overhead AES-SpMM exists to avoid.  The delta path
exploits three kinds of locality a ``BlockedPlan`` already has:

  * **block locality** — the plan's (strategy, width) table is per row
    block, so an edge delta re-ranks and re-samples only the blocks owning
    touched rows; untouched block segments are spliced through unchanged
    (zero-copy reshapes of the cached operand);
  * **fingerprint locality** — the plan-cache key is a combination of
    fixed-granularity per-block content digests
    (``repro.core.graph.csr_block_digests``), so the patched plan's key is
    rolled forward by re-digesting only touched digest blocks — and lands
    on exactly the fingerprint a cold tune of the patched graph computes;
  * **quantization locality** — the prepared uint operand keeps its global
    (x_min, x_max), so a feature update re-encodes only the touched rows
    (``repro.core.quantization.requantize_rows``).

Because per-block ranking is analytic and deterministic
(``cost_model.rank``), a patched plan is *bit-identical* to a cold
``tune_blocked`` of the patched graph under the same grid — configs,
operand bytes, buckets, and fingerprint all match (the differential suite
in ``tests/test_incremental.py`` and the ``delta-patched`` conformance
path pin this).  Degree-sorted plans (``layout="degree_sorted"``) compose
deltas through their *stored* permutation — the perm is frozen at tune
time, since re-deriving it from the patched degrees would reshuffle every
block and forfeit splice locality — so their operand bytes match a cold
tune *under the same perm*; the fingerprint (always natural-order) and
the executed outputs (inverse-permuted by the executor) still match the
natural path exactly.  What a patch skips is everything that makes cold tuning
slow: full-CSR hashing, per-block feature extraction and ranking of
untouched blocks, re-sampling of untouched segments, full re-quantization,
and all measurement (``benchmarks/incremental_update.py`` gates the >10x).

Concurrency: the patched plan is written through ``PlanCache.put`` whose
disk tier stages a tmp file and ``os.replace``s it over the entry — a
single atomic swap, so a concurrent loader observes the old version or the
new one, never a torn mix (``version`` counts applied patches).
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import (DIGEST_BLOCK_ROWS, BlockELL, apply_csr_deltas,
                              combine_block_digests, csr_block_digests,
                              partition_width_buckets)
from repro.tuning import calibration, cost_model, features as features_mod
from repro.tuning.cost_model import (CandidateConfig, DEFAULT_WIDTHS,
                                     MachineModel)
from repro.tuning.plan_cache import (BlockedPlan, PlanCache,
                                     features_fingerprint)


@dataclass(frozen=True)
class DeltaReport:
    """What one ``apply_edge_updates`` call actually did."""

    num_additions: int
    num_deletions: int
    touched_rows: int
    touched_blocks: tuple       # plan blocks re-ranked + re-sampled
    num_blocks: int             # total plan blocks (for the skipped ratio)
    touched_digest_blocks: tuple  # fingerprint digests recomputed
    requantized_rows: int
    fingerprint: str            # the patched plan's (new) cache key
    version: int                # the patched plan's version
    quant_drift: float = 0.0    # worst feature-range drift carried so far
    requant_refreshed: bool = False  # drift crossed the threshold: the
    # quantization range was re-derived and the full operand re-encoded

    @property
    def blocks_skipped(self) -> int:
        return self.num_blocks - len(self.touched_blocks)


def _block_grid(backend: str, quant_bits, strategies, widths,
                include_full: bool) -> list[CandidateConfig]:
    """The per-block candidate grid — must mirror ``tune_blocked`` exactly
    so a patched block's analytic winner equals the cold tune's."""
    candidates = [CandidateConfig(s, w, backend, quant_bits)
                  for s in strategies for w in widths]
    if include_full:
        candidates.append(CandidateConfig("full", 0, backend, quant_bits))
    return candidates


def _splice_block_ell(bell: BlockELL, csr, new_configs: dict) -> BlockELL:
    """Rebuild a BlockELL replacing only the blocks in ``new_configs``
    (block id -> (strategy, width)); every other segment is spliced through
    from the cached operand as a zero-copy reshape.

    Bit-equivalent to a cold ``sample_csr_to_block_ell`` of ``csr`` with
    the merged config table: untouched rows keep byte-identical
    ``col_ind``/``val`` slices (``apply_csr_deltas`` guarantees it) and
    every sampler addresses the global edge arrays *relative to the row
    pointer slice*, so shifted absolute offsets gather identical content.
    """
    from repro.core.sampling import sample_block_segment

    br = bell.block_rows
    row_nnz_host = np.asarray(csr.row_ptr[1:]) - np.asarray(csr.row_ptr[:-1])
    # Assemble on the host: per-block jnp slicing/concat costs a device
    # dispatch each (hundreds for a big plan — it dominated patch time);
    # numpy slices are views and the result crosses to the device once.
    old_val = np.asarray(bell.val)
    old_col = np.asarray(bell.col)
    old_live = np.asarray(bell.live_w)
    offsets = bell.slot_offsets()
    vals, cols, lives, widths, strategies = [], [], [], [], []
    for b in range(bell.num_blocks):
        if b in new_configs:
            strat, width = new_configs[b]
            v, c, live, w, s = sample_block_segment(
                csr, row_nnz_host, b, strat, width, br)
            v = np.asarray(v).reshape(-1)
            c = np.asarray(c).reshape(-1)
            live = np.asarray(live)
        else:
            off = offsets[b]
            n = br * bell.widths[b]
            v, c = old_val[off:off + n], old_col[off:off + n]
            live = old_live[b * br:(b + 1) * br]
            w, s = bell.widths[b], bell.strategies[b]
        vals.append(v)
        cols.append(c)
        lives.append(live)
        widths.append(w)
        strategies.append(s)
    from repro.kernels.gather import block_tail

    tail = block_tail(max(widths))
    vals.append(np.zeros(tail, old_val.dtype))
    cols.append(np.zeros(tail, np.int32))
    return BlockELL(
        val=jnp.asarray(np.concatenate(vals)),
        col=jnp.asarray(np.concatenate(cols)),
        live_w=jnp.asarray(np.concatenate(lives)), widths=tuple(widths),
        strategies=tuple(strategies), block_rows=br,
        num_rows=csr.num_rows, num_cols=csr.num_cols)


@obs.traced("incremental.apply_edge_updates")
def apply_edge_updates(plan: BlockedPlan, csr, additions=(), deletions=(),
                       *, features=None, requant_rows=(),
                       widths=DEFAULT_WIDTHS,
                       strategies=("aes", "afs", "sfs"),
                       include_full: bool = True,
                       max_buckets: int = 3,
                       machine: MachineModel | None = None,
                       accuracy_weight: float = 5.0,
                       cache: PlanCache | None = None,
                       verbose: bool = False):
    """Patch a cached ``BlockedPlan`` for a CSR edge delta.

    Args:
      plan: the cached plan for ``csr`` (``kind="block"``).
      csr: the CSR the plan was tuned for (the *pre*-delta graph).
      additions / deletions: edge deltas, ``(row, col[, val])`` /
        ``(row, col)`` tuples — :func:`~repro.core.graph.apply_csr_deltas`
        semantics (strict: every delta must change the graph).
      features: the dense feature matrix (current values, i.e. already
        updated when ``requant_rows`` is passed).  Only consulted for its
        width (the cost model's ``feat_dim``) and for re-quantization;
        required when the plan is quantized.
      requant_rows: feature rows whose values changed since the plan was
        quantized — only these rows of the prepared uint operand are
        re-encoded, with the stored global (x_min, x_max) range (values
        outside it clip; re-tune if the feature distribution drifts).
      widths / strategies / include_full / max_buckets / accuracy_weight:
        the tuning grid — pass the *same* knobs the plan was tuned with,
        or the patched blocks' decisions diverge from a cold re-tune.
      machine: cost model (default: the calibrated model, as in
        ``tune_blocked``).
      cache: when given, the patched plan is ``put()`` under its new
        fingerprint — an atomic versioned swap on the disk tier.

    Returns ``(new_plan, new_csr, report)``.  ``new_plan.version`` is
    ``plan.version + 1`` and its fingerprint/configs/operand bytes equal a
    cold ``tune_blocked(new_csr, ...)`` with the same grid (measurement
    fields are zeroed — patches never measure; that is most of the >10x).
    A no-op delta (empty additions, deletions, and requant_rows) returns
    ``plan`` itself unchanged.
    """
    if plan.kind != "block":
        raise ValueError("apply_edge_updates patches BlockedPlans only "
                         "(global TunedPlans have no block table)")
    bell = plan.bell
    if bell.num_rows != csr.num_rows or bell.num_cols != csr.num_cols:
        raise ValueError(
            f"plan shape ({bell.num_rows}, {bell.num_cols}) does not match "
            f"csr shape ({csr.num_rows}, {csr.num_cols})")

    # Base digests: from the plan when it carries them (cheap consistency
    # check against its fingerprint), else one full digest pass over the
    # pre-delta CSR — which doubles as a wrong-graph guard.
    if plan.block_digests:
        digests = list(plan.block_digests)
    else:
        digests = csr_block_digests(csr)
    if combine_block_digests(
            digests, csr.num_rows, csr.num_cols) != plan.fingerprint:
        raise ValueError("plan fingerprint does not match this CSR — "
                         "apply_edge_updates needs the exact pre-delta "
                         "graph the plan was tuned for")

    qf = plan.quantized
    quant_bits = qf.bits if qf is not None else None
    requant_rows = np.asarray(list(requant_rows), np.int64)
    if quant_bits is not None and features is None:
        raise ValueError("patching a quantized plan requires the current "
                         "feature matrix (pass `features=`)")
    if requant_rows.size and qf is None:
        raise ValueError("requant_rows given but the plan is not quantized")

    additions, deletions = list(additions), list(deletions)
    new_csr, touched = apply_csr_deltas(csr, additions, deletions)
    num_add, num_del = len(additions), len(deletions)

    if touched.size == 0 and requant_rows.size == 0:
        obs.count("incremental.noop_patches")
        return plan, csr, DeltaReport(
            num_additions=0, num_deletions=0, touched_rows=0,
            touched_blocks=(), num_blocks=bell.num_blocks,
            touched_digest_blocks=(), requantized_rows=0,
            fingerprint=plan.fingerprint, version=plan.version)

    # -- fingerprint: re-digest only touched digest blocks ----------------
    tdig = tuple(int(b) for b in np.unique(touched // DIGEST_BLOCK_ROWS))
    # Wrong-graph guard on the fast path: when the base digests came from
    # the plan itself, the fingerprint check above is a tautology — so
    # verify the touched blocks (which we must re-digest anyway) against
    # the actual pre-delta CSR before trusting it.
    if plan.block_digests:
        for b, d in zip(tdig, csr_block_digests(csr, blocks=tdig)):
            if digests[b] != d:
                raise ValueError(
                    f"digest block {b} of this CSR does not match the "
                    "plan — apply_edge_updates needs the exact pre-delta "
                    "graph the plan was tuned for")
    for b, d in zip(tdig, csr_block_digests(new_csr, blocks=tdig)):
        digests[b] = d
    new_fp = combine_block_digests(digests, new_csr.num_rows,
                                   new_csr.num_cols)

    # -- re-rank + re-sample only touched plan blocks ---------------------
    # A degree-sorted plan composes the delta through its *stored*
    # permutation: touched natural rows are remapped to their permuted
    # positions (the perm is frozen — re-deriving it from the patched
    # degrees would reshuffle every block and forfeit splice locality), so
    # only the permuted blocks owning touched rows re-rank and re-sample.
    # The fingerprint above stays natural-order, exactly as a cold tune
    # computes it.
    if plan.perm is not None:
        perm = np.asarray(plan.perm, np.int64)
        inv_perm = np.empty_like(perm)
        inv_perm[perm] = np.arange(perm.size, dtype=np.int64)
        from repro.core.graph import permute_csr_rows

        splice_csr = permute_csr_rows(new_csr, perm)
        tblk = tuple(int(b) for b in
                     np.unique(inv_perm[touched] // bell.block_rows))
    else:
        splice_csr = new_csr
        tblk = tuple(int(b) for b in np.unique(touched // bell.block_rows))
    if features is not None:
        feat_dim = int(np.shape(features)[1])
    else:
        feat_dim = 64   # tune_blocked's synthetic stand-in width
    if machine is None:
        machine = calibration.calibrated_machine_model() or MachineModel()
    grid = _block_grid(plan.backend, quant_bits, strategies, widths,
                       include_full)
    new_configs = {}
    for b, bf in zip(tblk, features_mod.extract_block_features(
            splice_csr, bell.block_rows, feat_dim=feat_dim, blocks=tblk)):
        best = cost_model.rank(bf, grid, machine, accuracy_weight)[0]
        new_configs[b] = (best.config.strategy, best.config.sh_width)
        if verbose:
            print(f"  patch block {b:4d} rows={bf.num_rows} nnz={bf.nnz} "
                  f"-> {best.config.key()}")

    new_bell = _splice_block_ell(bell, splice_csr, new_configs) if tblk \
        else bell
    # analytic bucket choice, as in tune_blocked's measurement-free branch
    # (finest partition within the launch budget); unchanged widths keep
    # the plan's existing — possibly measured — partition
    buckets = plan.buckets
    if new_bell.widths != bell.widths:
        buckets = partition_width_buckets(new_bell.widths, max_buckets)

    # -- re-quantize only touched feature rows ----------------------------
    new_qf, new_ffp = qf, plan.features_fp
    quant_drift = plan.quant_drift
    requant_refreshed = False
    if requant_rows.size:
        from repro.core.quantization import (DRIFT_THRESHOLD, quantize,
                                             range_drift, requantize_rows)

        # Track how far the updated feature distribution has moved from
        # the stored (x_min, x_max).  Gradual drift can stay "in range"
        # per patch while the data migrates to a sliver of the span (or
        # creeps past it, clipping) — the accumulated worst-case statistic
        # catches it, and past the threshold the whole operand is
        # re-encoded against a freshly derived range.
        quant_drift = max(quant_drift, range_drift(qf, features))
        if quant_drift > DRIFT_THRESHOLD:
            new_qf = quantize(jnp.asarray(features, jnp.float32), qf.bits)
            quant_drift = 0.0
            requant_refreshed = True
            obs.count("incremental.requant_refreshed")
        else:
            new_qf = requantize_rows(
                qf, requant_rows, np.asarray(features)[requant_rows])
        new_ffp = features_fingerprint(features)

    new_plan = replace(
        plan, bell=new_bell, fingerprint=new_fp,
        block_digests=tuple(digests), version=plan.version + 1,
        buckets=buckets, quantized=new_qf, features_fp=new_ffp,
        quant_drift=quant_drift,
        predicted_us=0.0, measured_spmm_us=0.0, measured_bucket_us=())
    if cache is not None:
        cache.put(new_plan)
    if obs.enabled():
        obs.count("incremental.patches")
        obs.count("incremental.blocks_touched", len(tblk))
        obs.count("incremental.blocks_skipped",
                  new_bell.num_blocks - len(tblk))
        obs.count("incremental.digest_blocks_touched", len(tdig))
        obs.count("incremental.requantized_rows", int(requant_rows.size))
    return new_plan, new_csr, DeltaReport(
        num_additions=num_add, num_deletions=num_del,
        touched_rows=int(touched.size), touched_blocks=tblk,
        num_blocks=new_bell.num_blocks, touched_digest_blocks=tdig,
        requantized_rows=int(requant_rows.size),
        fingerprint=new_fp, version=new_plan.version,
        quant_drift=float(quant_drift),
        requant_refreshed=requant_refreshed)
