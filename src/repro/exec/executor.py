"""PlanExecutor: the single owner of SpMM execution dispatch.

Before this module, "given a prepared operand and a backend, run the
aggregation" was decided in four places — ``tuning.measure.run_operand``
(global ELL), ``TunedPlan.run`` / ``BlockedPlan.run`` (plan guards +
blocked dispatch), ``core.aes_spmm`` (the manual strategy entry point),
and ``serving.engine._run_loop`` (per-shard serving).  Each grew its own
copy of the pallas/jax × float/quantized matrix, so adding an execution
path (the fused layer kernel, say) meant coordinated edits to all of
them.  ``PlanExecutor`` hoists that matrix into one class:

  * :meth:`run_ell` — global-ELL dispatch (pallas kernel / ref rowloop,
    fused-dequant or float), the body formerly in ``run_operand``;
  * :meth:`run_block` — BlockELL dispatch (width-bucketed pallas
    launches / ref oracle), formerly the tail of ``BlockedPlan.run``;
  * :meth:`run_plan` — plan-kind dispatch plus the content-hash guards
    that keep cached quantized operands honest;
  * :meth:`run_fused_layer` — the fused gather + dequant + SpMM + dense
    transform + activation path (one launch per layer, no HBM
    round-trip for the aggregation intermediate).

The old entry points still exist and now delegate here — the 17
pre-existing conformance paths pin that the move is behavior-preserving
against unmodified oracles.

Quantized-operand semantics, in one place
-----------------------------------------

A cached ``QuantizedFeatures`` stands for exactly the matrix it was
encoded from.  Two guards enforce that:

  * **hash guard** (plans): ``run_plan`` compares
    ``features_fingerprint(features)`` against the plan's stored
    ``features_fp`` and strips the quantized operand on mismatch —
    unknown operands take the float path.
  * **range guard** (``requant_guard=True``): the operand is *re-encoded*
    with the stored ``(x_min, x_max)`` via
    ``quantization.requantize_within_range`` — bit-exact for the matrix
    the range came from, exact-to-quantization for anything inside the
    range, and a float fallback when the range has drifted (re-encoding
    would clip).  This is how multi-layer inference serves hidden-layer
    activations through a quantized path without silently aggregating
    stale or clipped data — previously the manual pallas+quantized path
    served the stored matrix for *every* layer, ignoring the operand.
"""
from __future__ import annotations

from typing import Optional

from repro import obs
from repro.core.quantization import (QuantizedFeatures, dequantize,
                                     requantize_within_range)


def _dtype_tag(quantized: Optional[QuantizedFeatures]) -> str:
    return "float" if quantized is None else f"int{quantized.bits}"


def _guarded_requant(quantized, features, site: str):
    """Range-guard re-encode + the drift-fallback quality counter: how
    often a hidden-layer activation could ride the stored quantization
    range vs. fell back to the float path (or, for in-range operands whose
    distribution shrank past the drift threshold, got a freshly derived
    range — see ``quantization.requantize_within_range``).

    Returns ``(requanted, meta)``: the re-encoded operand (None on drift)
    and its kernel constants ``(scale, x_min)`` as host floats.  Every
    host read the guarded path makes happens here, in the guard's span."""
    with obs.trace("quant.requant_guard", site=site):
        requanted = requantize_within_range(quantized, features)
        if requanted is None:
            meta = None
        else:
            meta = (float(requanted.scale), float(requanted.x_min))
        if obs.enabled():
            obs.count("quant.requant_in_range" if requanted is not None
                      else "quant.requant_drift_fallback")
            # a refreshed range is a new pair of arrays, not the stored one
            if requanted is not None and requanted.x_min is not \
                    quantized.x_min:
                obs.count("quant.requant_range_refreshed")
            obs.count(f"quant.requant_{site}")
    return requanted, meta


def _meta(quantized: QuantizedFeatures, meta):
    """The dequant constants the kernels take: the guard's, else the
    operand's own."""
    return meta if meta is not None else (quantized.scale, quantized.x_min)


class PlanExecutor:
    """Uniform execution dispatch over prepared SpMM operands.

    Stateless apart from ``interpret`` (forwarded to every Pallas launch;
    ``None`` = interpret off-TPU, the kernels' own default), so one
    module-level instance serves every caller.
    """

    def __init__(self, interpret: Optional[bool] = None):
        self.interpret = interpret

    # ------------------------------------------------------------------
    # global ELL
    # ------------------------------------------------------------------
    def run_ell(self, ell, features, *, backend: str = "jax",
                quantized: Optional[QuantizedFeatures] = None,
                requant_guard: bool = False):
        """SpMM over a global fixed-width ELL operand.

        Args:
          ell: the sampled ``core.graph.ELL``.
          features: dense operand f32[nodes, feat]; a stray
            ``QuantizedFeatures`` is dequantized (float paths want the
            dense form).
          backend: "pallas" (kernel, fused dequant when quantized) or
            "jax"/"ref" (rowloop oracle).
          quantized: pre-quantized operand to serve instead of gathering
            float rows.  Callers that have already hash-verified it
            (plans) pass it as-is; callers serving arbitrary operands set
            ``requant_guard``.
          requant_guard: re-encode ``features`` with the quantized
            operand's stored range, falling back to float on range drift
            (see module docstring).
        """
        from repro.kernels import ops, ref

        if isinstance(features, QuantizedFeatures):
            features = dequantize(features)
        meta = None
        if quantized is not None and requant_guard:
            quantized, meta = _guarded_requant(quantized, features,
                                               "run_ell")
        with obs.trace("exec.run_ell", backend=backend,
                       dtype=_dtype_tag(quantized)):
            if obs.enabled():
                obs.count(
                    f"executor.run_ell.{backend}.{_dtype_tag(quantized)}")
            if backend == "pallas":
                if quantized is not None:
                    return ops.ell_spmm(
                        ell, quantized.q,
                        quantized_meta=_meta(quantized, meta),
                        interpret=self.interpret)
                return ops.ell_spmm(ell, features, interpret=self.interpret)
            x = dequantize(quantized) if quantized is not None else features
            return ref.ell_spmm_rowloop(ell.val, ell.col, x)

    # ------------------------------------------------------------------
    # BlockELL
    # ------------------------------------------------------------------
    def run_block(self, bell, features, *, backend: str = "jax",
                  quantized: Optional[QuantizedFeatures] = None,
                  buckets=None, inv_perm=None):
        """Width-bucketed block-dispatched SpMM over a BlockELL operand.

        Args:
          bell: the stitched ``core.graph.BlockELL``.
          features: dense operand (may be ``None`` when ``quantized``
            serves — plan callers enforce that pairing).
          backend: "pallas" (block kernel, one launch per width bucket)
            or "jax" (ref oracle).
          quantized: pre-quantized operand (already guard-verified).
          buckets: tuned width-bucket partition; ``None``/empty lets the
            kernel wrapper compute one.
          inv_perm: output row gather restoring natural order when the
            BlockELL was stitched over a row-permuted CSR (degree-sorted
            plans): row ``r`` of the result is permuted row
            ``inv_perm[r]``.  The input needs no permuting — columns are
            untouched by a row reorder — so this epilogue is the entire
            runtime cost of the layout.
        """
        with obs.trace("exec.run_block", backend=backend,
                       dtype=_dtype_tag(quantized)):
            if obs.enabled():
                obs.count(
                    f"executor.run_block.{backend}.{_dtype_tag(quantized)}")
            if backend == "pallas":
                from repro.kernels import ops

                if quantized is not None:
                    out = ops.block_ell_spmm(
                        bell, quantized.q,
                        quantized_meta=(quantized.scale, quantized.x_min),
                        buckets=buckets or None, interpret=self.interpret)
                else:
                    out = ops.block_ell_spmm(bell, features,
                                             buckets=buckets or None,
                                             interpret=self.interpret)
            else:
                from repro.kernels import ref

                if quantized is not None:
                    out = ref.quant_block_ell_spmm(bell, quantized)
                else:
                    out = ref.block_ell_spmm(bell, features)
            return out if inv_perm is None else out[inv_perm]

    # ------------------------------------------------------------------
    # plans
    # ------------------------------------------------------------------
    def run_plan(self, plan, features, *, assume_tuned: bool = False):
        """Execute a tuned plan (global or blocked) on ``features``.

        Owns the offline-quantization hash guard: a plan's cached
        quantized operand serves only the exact matrix it encodes
        (content-hash verified); any other operand takes the float path.
        ``assume_tuned=True`` (blocked plans) skips the per-call hash for
        serving engines that verified the match once at startup, and
        permits ``features=None`` on a quantized plan.
        """
        import numpy as np

        from repro.tuning.plan_cache import features_fingerprint

        if plan.kind == "block":
            if isinstance(features, QuantizedFeatures):
                features = np.asarray(dequantize(features))
            q = plan.quantized
            if q is not None and not assume_tuned \
                    and features_fingerprint(features) != plan.features_fp:
                q = None
                obs.count("executor.plan_hash_guard_miss")
            if q is None and features is None:
                raise ValueError("features=None requires a quantized plan "
                                 "and assume_tuned=True")
            with obs.trace("exec.run_plan", kind="block",
                           backend=plan.backend, dtype=_dtype_tag(q)):
                obs.count("executor.run_plan.block")
                return self.run_block(plan.bell, features,
                                      backend=plan.backend,
                                      quantized=q, buckets=plan.buckets,
                                      inv_perm=plan.inv_perm())
        q = plan.quantized
        if q is not None and not assume_tuned \
                and features_fingerprint(features) != plan.features_fp:
            q = None
            obs.count("executor.plan_hash_guard_miss")
        with obs.trace("exec.run_plan", kind="global",
                       strategy=plan.config.strategy,
                       backend=plan.config.backend, dtype=_dtype_tag(q)):
            obs.count(f"executor.run_plan.global.{plan.config.strategy}")
            return self.run_ell(plan.ell, features,
                                backend=plan.config.backend, quantized=q)

    # ------------------------------------------------------------------
    # fused layer
    # ------------------------------------------------------------------
    def run_fused_layer(self, ell, features, w, bias, *, relu: bool = True,
                        backend: str = "pallas",
                        quantized: Optional[QuantizedFeatures] = None,
                        requant_guard: bool = False, inv_perm=None):
        """One whole GNN layer — gather + (dequant) + SpMM + dense
        transform + activation — as a single execution step.

        On the pallas backend this is one kernel launch per layer
        (``kernels.fused_layer``): the aggregation intermediate stays in
        VMEM and never round-trips HBM.  The jax backend runs the exact
        ``ref.fused_layer`` oracle.  ``requant_guard`` carries the same
        drift semantics as :meth:`run_ell`, which is what lets layer 2+
        ride a quantized plan: in-range activations are re-encoded with
        the stored range, drifted ones fall back to float.  ``inv_perm``
        restores natural row order when ``ell`` was sampled from a
        row-permuted CSR (same epilogue semantics as :meth:`run_block`;
        row-wise activations commute with the row gather, so applying it
        after the fused transform is exact).
        """
        from repro.kernels import ops, ref

        if isinstance(features, QuantizedFeatures):
            features = dequantize(features)
        meta = None
        if quantized is not None and requant_guard:
            quantized, meta = _guarded_requant(quantized, features,
                                               "run_fused_layer")
        with obs.trace("exec.run_fused_layer", backend=backend,
                       dtype=_dtype_tag(quantized)):
            if obs.enabled():
                obs.count("executor.run_fused_layer."
                          f"{backend}.{_dtype_tag(quantized)}")
            if backend == "pallas":
                if quantized is not None:
                    out = ops.fused_layer_spmm(
                        ell, quantized.q, w, bias, relu=relu,
                        quantized_meta=_meta(quantized, meta),
                        interpret=self.interpret)
                else:
                    out = ops.fused_layer_spmm(ell, features, w, bias,
                                               relu=relu,
                                               interpret=self.interpret)
            else:
                x = dequantize(quantized) if quantized is not None \
                    else features
                out = ref.fused_layer(ell.val, ell.col, x, w, bias,
                                      relu=relu)
            return out if inv_perm is None else out[inv_perm]


_DEFAULT = PlanExecutor()


def default_executor() -> PlanExecutor:
    """The shared stateless executor every delegating entry point uses."""
    return _DEFAULT
