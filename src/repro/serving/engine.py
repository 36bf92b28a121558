"""`GNNServer`: sharded, micro-batched GNN inference over per-shard plans.

The single-call path (``aes_spmm``/``gnn.evaluate``) tunes one device's
plan and runs it synchronously.  This engine turns that into a serving
loop over a row-partitioned mesh:

  partition (``partition.py``)   1-D row shards + halo gather index
  per-shard plans (``plans.py``) ``tune_blocked`` per shard, cached under
                                 ``(fingerprint, "block", shard_meta)``
  execution (this module)        per request batch: gather each shard's
                                 operand, run its width-bucketed plan,
                                 concat the row outputs

Two execution modes:

  * ``mode="loop"`` — one launch per shard on a round-robin device
    assignment, with the *next* shard's operand dispatched before the
    current shard's compute is awaited (double buffering): on real
    accelerators the host->device feature transfer — uint8 when the plans
    are quantized, the paper's §3.1 loading win, now per shard — overlaps
    the previous shard's SpMM.  Works with any device count (shards may
    share a device), so a 1-CPU host can exercise a 4-shard layout.
  * ``mode="spmd"`` — one ``jax.shard_map`` call over a 1-D
    ``("shards",)`` mesh (one device per shard;
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` makes it
    CPU-testable).  Per-shard operands are padded to uniform shapes and
    the body runs one width-bucketed launch per shard — bucket boundaries
    are shared across shards (the block *table* is data; only the bucket
    max width is static), absent buckets padded with dead blocks whose
    rows land on a dump row.

Micro-batching: ``submit()`` enqueues requests, ``flush()`` executes the
whole queue in as few sharded passes as possible — SpMM is linear in the
dense operand's columns, so all float requests are served by **one**
column-concatenated pass, and requests for the graph's own feature matrix
(``x=None``) dedupe into a single pass over the cached (possibly
quantized) per-shard operands.  ``run_batch()`` is the same execution
path without the queue and without blocking on the device — the
non-blocking dispatch surface the continuous-batching runtime
(``repro.serving.runtime``) pipelines batches through.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.graph import CSR, partition_width_buckets
from repro.distributed.serving import SHARD_AXIS, serving_mesh, shard_devices
from repro.serving.partition import (CSRShard, concat_shard_outputs,
                                     halo_stats, partition_csr)
from repro.serving.plans import plan_shards
from repro.tuning.plan_cache import BlockedPlan, PlanCache, default_cache


def _device_put_plan(plan: BlockedPlan, device) -> BlockedPlan:
    """Commit a plan's operand arrays to its shard device.

    Array leaves only — the BlockELL's static metadata (widths/strategies
    tuples) must stay Python values, so no blanket tree ``device_put``.
    """
    bell = plan.bell._replace(
        val=jax.device_put(plan.bell.val, device),
        col=jax.device_put(plan.bell.col, device),
        live_w=jax.device_put(plan.bell.live_w, device))
    q = plan.quantized
    if q is not None:
        q = q._replace(q=jax.device_put(q.q, device),
                       x_min=jax.device_put(q.x_min, device),
                       x_max=jax.device_put(q.x_max, device))
    return dataclasses.replace(plan, bell=bell, quantized=q)


class _SpmdBundle:
    """Uniform-shape stacked operands for the ``shard_map`` path.

    Per-shard BlockELL segments are re-grouped under *global* width-bucket
    boundaries (one ``partition_width_buckets`` call over every shard's
    block widths) and padded so all shards present identical shapes:
    bucket ``k`` holds ``[num_shards, rcap_k, W_k]`` val/col arrays plus a
    ``[num_shards, rcap_k]`` destination-row index, where padding blocks
    are all-dead (val 0) and their rows target a dump row that is sliced
    off.  The SPMD body then runs one rowloop launch per bucket per shard
    and scatters into the shard's output rows — the same work the loop
    mode does, expressed as a single SPMD program.
    """

    def __init__(self, shards: Sequence[CSRShard],
                 plans: Sequence[BlockedPlan], features,
                 max_buckets: int = 3):
        num = len(shards)
        for p in plans:
            if getattr(p, "perm", None) is not None:
                # The bundle's destination-row index assumes block b's rows
                # land at [b*br, (b+1)*br) in natural order; a degree-sorted
                # plan's rows land at perm[those] instead and would need a
                # per-shard inverse scatter the SPMD body doesn't carry.
                raise ValueError(
                    "spmd mode does not support degree-sorted (row-"
                    "permuted) plans; use mode='loop' or layout='natural'")
        self.mesh = serving_mesh(num)
        self.num_shards = num
        self.rows = [s.num_rows for s in shards]
        self.rows_p = max(self.rows)
        self.gcap = max(s.csr.num_cols for s in shards)

        brs = {p.bell.block_rows for p in plans}
        if len(brs) != 1:
            raise ValueError(f"spmd mode needs one block_rows, got {brs}")
        br = brs.pop()

        gidx = np.zeros((num, self.gcap), np.int64)
        for s, sh in enumerate(shards):
            gidx[s, :len(sh.gather_index)] = sh.gather_index
        # Every per-shard stack is committed to the mesh along its leading
        # (shard) axis, so shard s's operands live on device s; only a
        # request's own feature matrix is scattered per call.
        from jax.sharding import NamedSharding, PartitionSpec

        sharded = NamedSharding(self.mesh, PartitionSpec(SHARD_AXIS))
        put = functools.partial(jax.device_put, device=sharded)
        self._gidx = jnp.asarray(gidx)

        # Global bucket bounds: each bucket covers widths in (prev, bound].
        all_widths = [w for p in plans for w in p.bell.widths]
        # segments are cut from host copies: one eager device slice per
        # block would compile once per segment size
        host = [(np.asarray(p.bell.val), np.asarray(p.bell.col),
                 p.bell.slot_offsets()) for p in plans]
        bounds = [bw for bw, _ in
                  partition_width_buckets(all_widths, max_buckets)]
        self.bucket_args: list[tuple] = []
        lo = 0
        for bw in bounds:
            sel = [[i for i, w in enumerate(p.bell.widths) if lo < w <= bw]
                   for p in plans]
            lo = bw
            cnt = max(len(ids) for ids in sel)
            if cnt == 0:
                continue
            rcap = cnt * br
            val = np.zeros((num, rcap, bw), np.float32)
            col = np.zeros((num, rcap, bw), np.int32)
            idx = np.full((num, rcap), self.rows_p, np.int32)  # dump row
            for s, p in enumerate(plans):
                hv, hc, offs = host[s]
                for j, bid in enumerate(sel[s]):
                    w = p.bell.widths[bid]
                    seg = slice(offs[bid], offs[bid] + br * w)
                    val[s, j * br:(j + 1) * br, :w] = hv[seg].reshape(br, w)
                    col[s, j * br:(j + 1) * br, :w] = hc[seg].reshape(br, w)
                    dest = np.arange(bid * br, (bid + 1) * br)
                    idx[s, j * br:(j + 1) * br] = np.where(
                        dest < self.rows[s], dest, self.rows_p)
            self.bucket_args.append(
                (put(val), put(col), put(idx)))

        # Resident operand for x=None requests: the quantized stack when
        # every shard's plan is quantized (uint8 across the wire) AND
        # verifiably encodes our gathered features (same one-time
        # features_fp check the loop mode makes — a stale disk entry
        # tuned on other features must not serve its operand), else the
        # float gather of the graph features.
        from repro.tuning.plan_cache import features_fingerprint

        self._quant = all(
            p.quantized is not None
            and features_fingerprint(s.gather(features)) == p.features_fp
            for s, p in zip(shards, plans))
        if self._quant:
            q = np.zeros((num, self.gcap, plans[0].quantized.q.shape[1]),
                         np.asarray(plans[0].quantized.q).dtype)
            scale = np.zeros((num, 1), np.float32)
            xmin = np.zeros((num, 1), np.float32)
            for s, p in enumerate(plans):
                q[s, :p.quantized.q.shape[0]] = np.asarray(p.quantized.q)
                scale[s, 0] = float(p.quantized.scale)
                xmin[s, 0] = float(p.quantized.x_min)
            self._resident = put(q)
            self._scale = put(scale)
            self._xmin = put(xmin)
        else:
            self._resident = put(jnp.asarray(features)[self._gidx])
            self._scale = self._xmin = None
        self._zeros = put(np.zeros((num, 1), np.float32))
        self._compiled: dict = {}

    def _fn(self, feat: int, quant: bool):
        """Compiled shard_map program for one (feat width, dtype) shape."""
        key = (feat, quant)
        if key in self._compiled:
            return self._compiled[key]
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ref

        rows_p, bucket_args = self.rows_p, self.bucket_args

        def body(x, scale, xmin, *flat):
            b = x[0]
            if quant:
                b = b.astype(jnp.float32) * scale[0, 0] + xmin[0, 0]
            out = jnp.zeros((rows_p + 1, b.shape[1]), jnp.float32)
            for k in range(len(bucket_args)):
                val, col, idx = flat[3 * k:3 * k + 3]
                out = out.at[idx[0]].add(
                    ref.ell_spmm_rowloop(val[0], col[0], b))
            return out[None, :rows_p]

        def spec(ndim):
            return P(SHARD_AXIS, *([None] * (ndim - 1)))

        in_specs = [spec(3), spec(2), spec(2)]
        in_specs += [spec(3), spec(3), spec(2)] * len(bucket_args)
        fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=spec(3), check_vma=False))
        self._compiled[key] = fn
        return fn

    def run(self, x=None):
        """One sharded pass: x=None serves the resident (possibly uint8)
        operand; a dense ``[num_nodes, F]`` matrix is gathered per shard
        and served on the float path."""
        zeros = self._zeros
        if x is None:
            quant = self._quant
            stack = self._resident
            scale = self._scale if quant else zeros
            xmin = self._xmin if quant else zeros
        else:
            quant = False
            stack = jax.device_put(jnp.asarray(x, jnp.float32)[self._gidx],
                                   zeros.sharding)
            scale = xmin = zeros
        flat = [a for args in self.bucket_args for a in args]
        out = self._fn(int(stack.shape[-1]), quant)(stack, scale, xmin, *flat)
        # Trim ragged shard tails on device — no host round trip per
        # request; the equal-rows case is a pure reshape.
        if all(n == self.rows_p for n in self.rows):
            return out.reshape(self.num_shards * self.rows_p, -1)
        return jnp.concatenate(
            [out[s, :n] for s, n in enumerate(self.rows)], axis=0)


class GNNServer:
    """Sharded, batched GNN inference engine over mesh-aware plans.

    Args:
      csr: the adjacency (e.g. ``dataset.gcn_adj``).
      features: the graph's dense node-feature matrix ``[num_nodes, F]``
        — tuned against, optionally pre-quantized into the per-shard
        plans, and served by ``submit(x=None)`` requests.
      num_shards: row shards (default: one per local device).
      mode: ``"loop"`` (per-shard launches, any device count) or
        ``"spmd"`` (one ``shard_map`` call, one device per shard).
      quant: pre-quantize each shard's operand to this bit width (8/16);
        serving then moves uint8 features and fuses Eq. 2 into the gather.
      cache: plan cache (default process-wide).  Point it at a disk dir
        (``$REPRO_PLAN_CACHE_DIR``) and a restarted server re-assembles
        every shard plan from disk without re-tuning.
      tune_kwargs: forwarded to each shard's ``tune_blocked`` call.
      devices: explicit device list for the loop mode's round-robin.

    Serving API: ``submit(x=None) -> ticket``, ``flush() -> [results]``,
    or ``aggregate(x=None)`` for a one-shot request.  ``x=None`` requests
    the aggregation of the server's own feature matrix (the cached —
    possibly quantized — fast path); a dense ``[num_nodes, F]`` operand
    (a hidden-layer activation, an updated table) takes the float path.
    """

    def __init__(self, csr: CSR, features, *,
                 num_shards: Optional[int] = None,
                 mode: str = "loop",
                 quant: Optional[int] = None,
                 cache: Optional[PlanCache] = None,
                 tune_kwargs: Optional[dict] = None,
                 devices=None,
                 max_buckets: int = 3):
        if mode not in ("loop", "spmd"):
            raise ValueError(f"unknown mode {mode!r} "
                             "(expected 'loop' or 'spmd')")
        if num_shards is None:
            num_shards = min(jax.device_count(), csr.num_rows)
        self.mode = mode
        self.num_shards = int(num_shards)
        self.cache = cache if cache is not None else default_cache()
        self.features = jnp.asarray(features, jnp.float32)
        self.shards = partition_csr(csr, self.num_shards)
        self.mesh_shape = (self.num_shards,)
        self._quant = quant
        self._tune_kwargs = dict(tune_kwargs or {})
        self._requested_devices = devices
        self._max_buckets = max_buckets
        self.plans = plan_shards(
            self.shards, self.features, mesh_shape=self.mesh_shape,
            quant=quant, cache=self.cache, tune_kwargs=tune_kwargs)
        self._prepare_execution()

        self._queue: list = []
        self._closed = False
        self._features_fp: Optional[str] = None  # lazy content hash
        self.stats = {"requests": 0, "flushes": 0, "sharded_passes": 0,
                      "rows_served": 0, "resident_dedupes": 0,
                      "edge_updates": 0}

    def _prepare_execution(self) -> None:
        """(Re)build the mode-specific execution state from the current
        ``self.shards`` / ``self.plans`` — called at init and again after
        :meth:`apply_edge_updates` swaps patched shards/plans in."""
        self._bundle = None
        if self.mode == "spmd":
            self._bundle = _SpmdBundle(self.shards, self.plans,
                                       self.features, self._max_buckets)
            self._devices = None
        else:
            self._devices = shard_devices(self.num_shards,
                                          self._requested_devices)
            self.plans = [_device_put_plan(p, d)
                          for p, d in zip(self.plans, self._devices)]
            # One-time tuned-operand verification per shard, so the
            # request hot path never hashes: a quantized plan whose
            # features_fp matches our gather serves its uint8 operand
            # directly (no float resident at all); one tuned on *other*
            # features (a stale disk entry) has its quantized operand
            # dropped from this server's copy and serves the float path.
            self._resident = []
            for i, (s, d) in enumerate(zip(self.shards, self._devices)):
                plan = self.plans[i]
                gathered = s.gather(self.features)
                if plan.quantized is not None:
                    from repro.tuning.plan_cache import features_fingerprint

                    if features_fingerprint(gathered) == plan.features_fp:
                        self._resident.append(None)   # uint8 operand serves
                        continue
                    self.plans[i] = dataclasses.replace(
                        plan, quantized=None, features_fp="")
                self._resident.append(jax.device_put(gathered, d))
            # Dense (non-resident) requests can never match a quantized
            # plan's tuned operand — serve them through a quantless view
            # so the hot path skips the content hash entirely.
            self._float_plans = [
                dataclasses.replace(p, quantized=None, features_fp="")
                if p.quantized is not None else p for p in self.plans]

    def apply_edge_updates(self, additions=(), deletions=()) -> dict:
        """Patch the live deployment for a graph edge delta.

        Routes the global delta to the shards owning the touched rows
        (``repro.serving.plans.apply_edge_updates_sharded``): those shards'
        plans are patched in place (or, on halo growth, re-tuned), every
        other shard's plan is untouched, and the execution state (device
        placement, resident operands, the spmd bundle) is rebuilt from the
        swapped-in shards/plans.  Pending submitted tickets are served by
        the *patched* graph at the next ``flush()``.

        Returns the routing report (patched/retuned/untouched shard ids +
        per-shard ``DeltaReport``\\s).
        """
        from repro.serving.plans import apply_edge_updates_sharded

        self.shards, self.plans, report = apply_edge_updates_sharded(
            self.shards, self.plans, additions, deletions,
            features=self.features, mesh_shape=self.mesh_shape,
            quant=self._quant, cache=self.cache,
            tune_kwargs=self._tune_kwargs)
        self._prepare_execution()
        self.stats["edge_updates"] += 1
        return report

    # -- submission ------------------------------------------------------

    def validate_operand(self, x):
        """Validate one request operand at enqueue time, returning its
        ``float32`` view (``None`` passes through: the cached features).

        Rejections happen here — before the request is admitted — with a
        ``ValueError`` naming the problem, instead of a shape/dtype error
        surfacing deep inside the batched sharded pass (where it would
        take the whole micro-batch down with it): a closed server, a
        non-2D operand, a feature-dim (node-count) mismatch, or a
        non-real dtype (complex/object/strings cannot be aggregated).
        """
        if self._closed:
            raise ValueError("server is closed (no further submissions)")
        if x is None:
            return None
        dtype = getattr(x, "dtype", None)
        if dtype is None:
            x = np.asarray(x)
            dtype = x.dtype
        if not (np.issubdtype(dtype, np.floating)
                or np.issubdtype(dtype, np.integer)
                or np.issubdtype(dtype, np.bool_)):
            raise ValueError(
                f"operand dtype {dtype} is not a real numeric dtype "
                "(expected float/int/bool, castable to float32)")
        if getattr(x, "ndim", None) != 2:
            raise ValueError(
                f"operand must be 2-D [num_nodes, F], got ndim="
                f"{getattr(x, 'ndim', None)}")
        if int(x.shape[0]) != int(self.features.shape[0]):
            raise ValueError(
                f"operand shape {tuple(x.shape)} does not match "
                f"[num_nodes={self.features.shape[0]}, F]")
        return jnp.asarray(x, jnp.float32)

    def _is_resident_operand(self, x) -> bool:
        """True when ``x`` is (content-equal to) the server's own feature
        matrix — the same content-hash guard the plan cache uses
        (``features_fingerprint``), not object identity, so an
        equal-but-distinct copy (``jnp.asarray`` round trip, a
        deserialized request payload) still takes the cached/quantized
        fast path.  Shape/dtype gate first: a hidden-layer activation has
        a different column count and never pays the O(N*F) hash."""
        if x is self.features:
            return True
        if tuple(x.shape) != tuple(self.features.shape) \
                or x.dtype != self.features.dtype:
            return False
        from repro.tuning.plan_cache import features_fingerprint

        if self._features_fp is None:
            self._features_fp = features_fingerprint(self.features)
        return features_fingerprint(x) == self._features_fp

    def submit(self, x=None) -> int:
        """Enqueue a request; returns its ticket (index into the next
        ``flush()`` result list).  Invalid operands and post-``close()``
        submissions raise ``ValueError`` here, at enqueue time.

        A dense operand content-equal to the server's feature matrix is
        deduped to the ``x=None`` fast path (see
        :meth:`_is_resident_operand`)."""
        x = self.validate_operand(x)
        if x is not None and self._is_resident_operand(x):
            self.stats["resident_dedupes"] += 1
            x = None
        ticket = len(self._queue)
        self._queue.append(x)
        return ticket

    def run_batch(self, batch: Sequence) -> list:
        """Execute one micro-batch of operands *without blocking on the
        device*: returns one asynchronously-dispatched ``[num_rows, F_i]``
        array per entry, in order (jax arrays are futures until forced —
        callers that need host values ``block_until_ready``).

        This is the engine's non-blocking dispatch path: ``flush()`` is a
        thin wrapper over it, and the continuous-batching runtime
        (``repro.serving.runtime``) calls it directly so the next batch
        can be assembled while this one is still on device.

        All float operands ride one column-concatenated sharded pass
        (SpMM is linear in B's columns); ``None`` entries (the server's
        own feature matrix) dedupe into one pass over the cached —
        possibly quantized — per-shard operands.
        """
        batch = list(batch)
        if not batch:
            return []
        self.stats["requests"] += len(batch)
        self.stats["flushes"] += 1
        return self._run_batch_inner(batch)

    @obs.traced("engine.run_batch")
    def _run_batch_inner(self, batch: list) -> list:

        results: list = [None] * len(batch)
        dense = [(t, x) for t, x in enumerate(batch) if x is not None]
        if any(x is None for x in batch):
            out = self._run(None)
            for t, x in enumerate(batch):
                if x is None:
                    results[t] = out
        if dense:
            widths = [int(x.shape[1]) for _, x in dense]
            cat = self._run(jnp.concatenate([x for _, x in dense], axis=1)
                            if len(dense) > 1 else dense[0][1])
            off = 0
            for (t, _), w in zip(dense, widths):
                results[t] = cat[:, off:off + w]
                off += w
        self.stats["rows_served"] += \
            int(self.features.shape[0]) * len(batch)
        return results

    def flush(self) -> list:
        """Execute the queued micro-batch; returns one ``[num_rows, F_i]``
        result per ticket, in submission order (see :meth:`run_batch`)."""
        queue, self._queue = self._queue, []
        return self.run_batch(queue)

    def close(self) -> list:
        """Drain: execute any pending micro-batch, then refuse further
        submissions (``submit`` raises ``ValueError``).  Returns the
        drained results (empty when nothing was pending).  Idempotent."""
        results = self.flush() if self._queue else []
        self._closed = True
        return results

    def aggregate(self, x=None):
        """One-shot request, independent of the micro-batch queue: any
        tickets already submitted stay pending for the next ``flush()``."""
        pending, self._queue = self._queue, []
        try:
            ticket = self.submit(x)
            return self.flush()[ticket]
        finally:
            self._queue = pending

    # -- execution -------------------------------------------------------

    def _run(self, x):
        self.stats["sharded_passes"] += 1
        if self._bundle is not None:
            return self._bundle.run(x)
        return self._run_loop(x)

    def _operand(self, s: int, x):
        if x is None:
            return self._resident[s]
        return jax.device_put(self.shards[s].gather(x), self._devices[s])

    def _run_loop(self, x):
        """Per-shard launches with double-buffered operand dispatch: shard
        ``s+1``'s gather/transfer is issued before shard ``s``'s compute
        is consumed, so data loading overlaps compute across devices.
        ``x=None`` requests run ``assume_tuned`` — the init-time
        verification already pinned each resident operand to its plan, so
        no per-request content hashing happens here."""
        from repro.exec import default_executor

        executor = default_executor()
        plans = self.plans if x is None else self._float_plans
        outs = []
        cur = self._operand(0, x)
        for s in range(self.num_shards):
            nxt = self._operand(s + 1, x) if s + 1 < self.num_shards \
                else None
            outs.append(executor.run_plan(plans[s], cur,
                                          assume_tuned=x is None))
            cur = nxt
        return concat_shard_outputs(outs)

    # -- introspection ---------------------------------------------------

    def halo_stats(self) -> dict:
        """Partition quality: halo rows gathered per shard."""
        return halo_stats(self.shards)

    def plan_summary(self) -> list[dict]:
        """Per-shard plan digest for reports and the ``--smoke`` CLI."""
        out = []
        for sh, p in zip(self.shards, self.plans):
            out.append({
                "shard": sh.shard_idx,
                "rows": sh.num_rows,
                "halo": sh.num_halo,
                "blocks": p.bell.num_blocks,
                "layout": p.row_layout,
                "widths": list(p.bell.widths),
                "buckets": [[w, len(ids)] for w, ids in p.buckets],
                "quant_bits": None if p.quantized is None
                else p.quantized.bits,
                "shard_meta": {"mesh": list(p.shard_meta[0]),
                               "shard": p.shard_meta[1],
                               "of": p.shard_meta[2]},
            })
        return out
