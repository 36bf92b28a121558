"""Public AES-SpMM API: the paper's contribution as one composable call.

    aes_spmm(csr, features, sh_width=128,
             strategy="auto" | "aes" | "afs" | "sfs" | "full",
             backend="ref" | "jax" | "pallas" | "pallas_fused",
             quantized=None | QuantizedFeatures)

``strategy`` selects the paper's adaptive scheme or the ES-SpMM baselines;
``"full"`` disables sampling (cuSPARSE/GE-SpMM role).  ``backend`` selects
the execution path; all paths agree to float tolerance (tests assert it).

``strategy="auto"`` hands the whole knob set to ``repro.tuning``: the tuner
picks (strategy, W, backend, quant) per graph from sparsity features +
microbenchmarks, and the sampled ELL operand is cached under the graph's
fingerprint — repeated calls with the same graph skip sampling entirely.
``sh_width``/``backend`` are then ignored (the plan carries its own);
``quantized`` feeds the blocked tuner under ``granularity="block"`` but is
ignored for graph granularity, where the tuner makes its own quant choice.
Pass ``plan_cache`` to control cache scope (default: process-wide).

``granularity="block"`` (auto only) tunes (strategy, W) *per fixed-size row
block* instead of once per graph and serves from a stitched mixed-width
BlockELL operand — the right tool for bimodal/power-law degree
distributions, where one global width over-samples the dense head or wastes
width on the sparse tail.  The blocked path is quantization-aware: pass
``quantized=`` (or ``tune_kwargs=dict(quant=8)``) and the plan caches the
uint8 operand, serving it through a fused dequantize-then-aggregate gather
in width-bucketed kernel launches.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.graph import CSR, ELL, ell_live_widths, pad_csr_to_ell
from repro.core.quantization import QuantizedFeatures, dequantize
from repro.core.sampling import BAND_SAMPLES, STRATEGIES, sampled_rows


@functools.partial(jax.jit, static_argnames=("sh_width", "by_path"))
def _edge_counts(val, col, row_ptr, *, sh_width: int, by_path: bool):
    """(kept, dropped) edges of one sampled operand, on the device:
    live slots, and the rest of the ``nnz`` (at least 0, because AES may
    duplicate hub edges).  With ``by_path``, then the rows the Pallas
    sampler copied whole, sampled, and copied whole with a DMA of their
    own (``kernels.aes_sample.row_paths``), the run DMAs of its sampled
    rows and those rows per Table-1 band
    (``core.sampling.sampled_rows``)."""
    kept = ell_live_widths(val, col).sum()
    counts = (kept, jnp.maximum(row_ptr[-1] - kept, 0))
    if by_path:
        from repro.kernels.aes_sample import row_paths

        counts += row_paths(row_ptr, sh_width) \
            + sampled_rows(row_ptr, sh_width)
    return jnp.stack(counts)


def sample(csr: CSR, sh_width: int, strategy: str = "aes",
           backend: str = "jax") -> ELL:
    """Sampling pre-pass producing the ELL operand."""
    with obs.trace("sample", strategy=strategy, backend=backend):
        kernel = backend == "pallas" and strategy == "aes"
        if strategy == "full":
            ell = pad_csr_to_ell(csr)
        elif kernel:
            from repro.kernels import ops

            ell = ops.aes_sample(csr, sh_width)
        else:
            fn = STRATEGIES[strategy]
            val, col = fn(csr.row_ptr, csr.col_ind, csr.val, sh_width)
            ell = ELL(val, col, csr.num_cols)
        if obs.enabled():
            # the paper's accuracy-vs-speed dial, as counters: how many
            # edges the sampler kept vs. discarded on this call, and the
            # kernel's rows by path, counted on the device and read only
            # when the counters are read
            counts = _edge_counts(ell.val, ell.col, csr.row_ptr,
                                  sh_width=sh_width, by_path=kernel)
            obs.count("sampler.calls")
            obs.count(f"sampler.calls.{strategy}")
            names = ("edges_kept", "edges_dropped", "rows_whole",
                     "rows_sampled", "rows_own_dma", "sample_runs") \
                + tuple(f"rows_band{c}" for c in BAND_SAMPLES)
            obs.count_deferred(
                tuple(f"sampler.{n}" for n in names[:counts.shape[0]]),
                counts, max(csr.nnz, max(BAND_SAMPLES) * csr.num_rows))
    return ell


def aes_spmm(csr: CSR, features, sh_width: int = 128, *,
             strategy: str = "aes", backend: str = "jax",
             granularity: str = "graph",
             quantized: Optional[QuantizedFeatures] = None,
             interpret=None, plan_cache=None, tune_kwargs=None):
    """Sampled aggregation C = sample(A) @ B (paper Alg. 1 end to end).

    Args:
      csr: adjacency in CSR form (see ``repro.core.graph.CSR``).
      features: dense operand B, f32[num_nodes, feat].
      sh_width: shared-memory width W (ignored for strategy "full"/"auto").
      strategy: "aes" | "afs" | "sfs" | "full" | "auto".
      backend: "ref" | "jax" | "pallas" | "pallas_fused" (ignored for
        "auto" — the tuned plan carries its own backend).
      granularity: "graph" (default) tunes one global config; "block"
        (auto only) tunes per row block and serves a mixed-width BlockELL.
      quantized: optional pre-quantized B (int8/int16 gather path).  Under
        ``strategy="auto"`` it is honored for ``granularity="block"`` (the
        plan caches it) and ignored for graph granularity.
      plan_cache / tune_kwargs: auto-mode cache scope and ``tune()`` /
        ``tune_blocked()`` overrides.

    Returns f32[num_rows, feat].
    """
    from repro.kernels import ops

    if granularity not in ("graph", "block"):
        raise ValueError(f"unknown granularity {granularity!r} "
                         "(expected 'graph' or 'block')")
    if strategy == "auto":
        if isinstance(features, QuantizedFeatures):
            # normalize: the tuner wants the dense reconstruction as the
            # serving operand and the quantized matrix as the quant source
            if quantized is None:
                quantized = features
            features = dequantize(features)
        if granularity == "block":
            from repro.tuning.autotune import tune_blocked

            kw = dict(tune_kwargs or {})
            if quantized is not None:
                # pre-quantized B rides into the blocked plan: the tuner
                # reuses it (no second lossy pass) and serves the
                # fused-dequant path
                kw.setdefault("quant", quantized)
            plan = tune_blocked(csr, features, cache=plan_cache, **kw)
        else:
            from repro.tuning.autotune import tune

            plan = tune(csr, features, cache=plan_cache,
                        **(tune_kwargs or {}))
        return plan.run(features)
    if granularity != "graph":
        raise ValueError(
            'granularity="block" requires strategy="auto" (per-block '
            "configs are the tuner's to pick)")

    if quantized is not None and backend != "pallas":
        features = dequantize(quantized)

    if backend == "pallas_fused":
        if strategy != "aes":
            raise ValueError("fused kernel implements the AES strategy only")
        if quantized is not None:
            features = dequantize(quantized)
        return ops.fused_aes_spmm(csr, features, sh_width, interpret=interpret)

    ell = sample(csr, sh_width, strategy,
                 backend="jax" if backend == "ref" else backend)

    if backend not in ("ref", "jax", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    from repro.exec import PlanExecutor

    # beyond-paper: on the pallas backend the dequant is fused into the
    # B-row gather.  requant_guard re-encodes `features` with the stored
    # range (bit-exact when features IS the matrix `quantized` encodes) so
    # a hidden-layer activation is never served stale int8 data — it
    # re-quantizes in range, or falls back to the float gather on drift.
    return PlanExecutor(interpret=interpret).run_ell(
        ell, features, backend="jax" if backend == "ref" else backend,
        quantized=quantized if backend == "pallas" else None,
        requant_guard=True)
