"""Smoke run of GCN inference on one TPU chip, through the repo's own
entry points, at the published size of ogbn-arxiv.

    python chip_smoke.py                 # one chip: train + four inference phases
    python chip_smoke.py --four-chips    # sharded serving over four chips only

One chip: ``make_dataset("ogbn-arxiv", scale=1.0)`` (169,343 nodes, average
degree 13.7, F=128), a few exact-aggregation training steps of the paper's
``gcn-ogbn-arxiv`` configuration, then four inference phases, each checked
against the jnp path on the same operand:

  1. ``strategy="aes", backend="pallas"``: the sampling and ELL SpMM kernels;
  2. the same with ``quantize_bits=8``: the fused-dequant uint8 gather;
  3. ``fuse_layers=True``: the fused layer kernel;
  4. ``strategy="auto", granularity="block"`` with int8 tuning: the tuner
     on the chip and the BlockELL kernel.

``--four-chips`` runs only ``GNNServer`` over four shards in loop and spmd
mode against the exact CSR SpMM.  Every phase runs in this one process.  A
phase that fails ends the run: the script exits non-zero and prints no
result line.  Without a TPU it exits 1 before any work.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
W = 128                     # the paper's shared-memory width (sh_width)
EPOCHS = 20                 # exact-aggregation training steps
SEED = 0
RTOL = ATOL = 1e-4          # the conformance suite's float tolerance


def log(msg: str) -> None:
    print(msg, flush=True)


def check_close(label: str, got, want, *, atol: float = ATOL,
                rtol: float = RTOL) -> float:
    """Max abs error of ``got`` vs ``want``; raises past ``atol + rtol *
    |want|`` anywhere (the ``assert_allclose`` rule)."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{label}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{label}: non-finite output")
    err = np.abs(got - want)
    excess = float(np.max(err - (atol + rtol * np.abs(want)), initial=-1.0))
    log(f"  {label}: max_abs_err={float(err.max(initial=0.0))!r} "
        f"(atol={atol!r}, rtol={rtol!r})")
    if excess > 0:
        raise AssertionError(f"{label}: error over tolerance by {excess!r}")
    return float(err.max(initial=0.0))


def lowered_has_kernel(fn, *args) -> bool:
    """Whether ``fn`` lowers to a compiled Mosaic kernel (not interpreted)."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def device_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, jax.Array))


def one_chip(ds):
    """Train, then run the four inference phases on ``ds``."""
    import jax
    import numpy as np

    from repro import obs
    from repro.configs.gnn_paper import PAPER_GNN_CONFIGS
    from repro.core.aes_spmm import aes_spmm, sample
    from repro.core.graph import ELL
    from repro.core.quantization import dequantize, quantize
    from repro.exec import PlanExecutor
    from repro.gnn import evaluate, train_model
    from repro.kernels import ops, ref
    from repro.tuning import PlanCache
    from repro.tuning.autotune import tune_blocked

    cfg = PAPER_GNN_CONFIGS["gcn-ogbn-arxiv"]
    adj, x = ds.gcn_adj, ds.features
    log(f"data: {ds.spec.name} nodes={adj.num_rows} edges={int(adj.nnz)} "
        f"F={x.shape[1]} device_bytes={device_bytes(ds)}")

    t0 = time.perf_counter()
    params, exact_acc = train_model(ds, cfg.model, hidden=cfg.hidden,
                                    epochs=EPOCHS, seed=SEED)
    log(f"train: {cfg.model} hidden={cfg.hidden} epochs={EPOCHS} "
        f"exact_test_acc={exact_acc!r} "
        f"(smoke timing, not a benchmark metric: "
        f"{time.perf_counter() - t0:.1f}s incl. compile)")
    if not np.isfinite(exact_acc):
        raise AssertionError("training produced a non-finite accuracy")

    ex = PlanExecutor()
    result = {"exact_acc": exact_acc}

    # -- 1. AES sampling + ELL SpMM kernels --------------------------------
    log("phase 1: strategy=aes backend=pallas")
    ell = sample(adj, W, "aes", backend="pallas")
    ell_ref = sample(adj, W, "aes", backend="jax")
    bad = int(np.sum(np.asarray(ell.col) != np.asarray(ell_ref.col)))
    log(f"  aes_sample vs jax sampler: {bad} column mismatches")
    if bad:
        raise AssertionError("aes_sample kernel disagrees with the sampler")
    check_close("aes_sample val", ell.val, ell_ref.val, atol=0.0, rtol=0.0)
    got = aes_spmm(adj, x, W, strategy="aes", backend="pallas")
    want = ref.ell_spmm_rowloop(ell_ref.val, ell_ref.col, x)
    result["aes_err"] = check_close("aes spmm", got, want)

    def spmm(v, c, b):
        return ops.ell_spmm(ELL(v, c, adj.num_cols), b)

    if not lowered_has_kernel(spmm, ell.val, ell.col, x):
        raise AssertionError("ell_spmm did not lower to tpu_custom_call")
    t0 = time.perf_counter()
    compiled = jax.jit(spmm).lower(ell.val, ell.col, x).compile()
    compile_s = time.perf_counter() - t0
    compiled(ell.val, ell.col, x).block_until_ready()
    t0 = time.perf_counter()
    compiled(ell.val, ell.col, x).block_until_ready()
    log(f"  smoke timing, not a benchmark metric: ell_spmm compile "
        f"{compile_s:.2f}s, one warmed call "
        f"{time.perf_counter() - t0:.4f}s")
    result["aes_acc"] = evaluate(ds, cfg.model, params, sh_width=W,
                                 strategy="aes", backend="pallas")
    log(f"  accuracy: aes/pallas={result['aes_acc']!r} exact={exact_acc!r}")

    # -- 2. fused-dequant uint8 gather ---------------------------------------
    log("phase 2: strategy=aes backend=pallas quantize_bits=8")
    qf = quantize(x, 8)
    got = aes_spmm(adj, x, W, strategy="aes", backend="pallas", quantized=qf)
    want = ref.ell_spmm_rowloop(ell_ref.val, ell_ref.col, dequantize(qf))
    result["quant_err"] = check_close(
        "int8 spmm", got, want, atol=float(qf.scale) * 0.5 + 1e-5)
    result["quant_acc"] = evaluate(ds, cfg.model, params, sh_width=W,
                                   strategy="aes", backend="pallas",
                                   quantize_bits=8)
    log(f"  accuracy: aes/pallas/int8={result['quant_acc']!r} "
        f"exact={exact_acc!r}")

    # -- 3. fused layer kernel ------------------------------------------------
    # The kernel's in-VMEM matmul runs at HIGHEST precision; the unfused
    # reference runs there too, so both sides are f32-exact matmuls.
    log("phase 3: fuse_layers=True backend=pallas")
    with jax.default_matmul_precision("highest"):
        h = ex.run_fused_layer(ell, x, params.w1, params.b1, relu=True,
                               backend="pallas")
        got = ex.run_fused_layer(ell, h, params.w2, params.b2, relu=False,
                                 backend="pallas")
        h_ref = ref.fused_layer(ell.val, ell.col, x, params.w1, params.b1)
        want = ref.fused_layer(ell.val, ell.col, h_ref, params.w2,
                               params.b2, relu=False)
    check_close("fused layer 1", h, h_ref)
    result["fused_err"] = check_close("fused logits", got, want)
    result["fused_acc"] = evaluate(ds, cfg.model, params, sh_width=W,
                                   strategy="aes", backend="pallas",
                                   fuse_layers=True)
    log(f"  accuracy: fused/pallas={result['fused_acc']!r} "
        f"exact={exact_acc!r}")

    # -- 4. tuner + BlockELL kernel ------------------------------------------
    log("phase 4: strategy=auto granularity=block quant=8")
    cache = PlanCache()
    got = aes_spmm(adj, x, strategy="auto", granularity="block",
                   plan_cache=cache, tune_kwargs=dict(quant=8))
    plan = tune_blocked(adj, x, cache=cache, quant=8)   # the cached plan
    if cache.stats.misses != 1:
        raise AssertionError(f"expected one tune, got {cache.stats}")
    want = ex.run_block(plan.bell, x, backend="jax",
                        quantized=plan.quantized, inv_perm=plan.inv_perm())
    atol = ATOL if plan.quantized is None \
        else float(plan.quantized.scale) * 0.5 + 1e-5
    result["block_err"] = check_close("auto block spmm", got, want,
                                      atol=atol)
    widths = sorted(set(plan.bell.widths))
    log(f"  plan: backend={plan.backend} layout={plan.row_layout} "
        f"blocks={plan.bell.num_blocks} block_rows={plan.block_rows} "
        f"widths={widths} strategies={sorted(set(plan.bell.strategies))} "
        f"buckets={[[w, len(ids)] for w, ids in plan.buckets]} "
        f"quant_bits={None if plan.quantized is None else plan.quantized.bits}")
    result["plan"] = {"backend": plan.backend, "widths": widths}
    result["block_acc"] = evaluate(ds, cfg.model, params, strategy="auto",
                                   granularity="block", quantize_bits=8,
                                   plan_cache=cache)
    log(f"  accuracy: auto/block/int8={result['block_acc']!r} "
        f"exact={exact_acc!r}")

    counters = obs.snapshot().get("counters", {})
    for key in ("executor.run_ell.pallas.float", "executor.run_ell.pallas.int8",
                "executor.run_fused_layer.pallas.float",
                f"executor.run_block.{plan.backend}.int8"):
        log(f"  counter {key}={counters.get(key, 0)}")
        if not counters.get(key):
            raise AssertionError(f"counter {key} is zero")
    if plan.backend != "pallas":
        raise AssertionError(f"tuner chose backend {plan.backend!r} on TPU")
    return result


def four_chips(ds):
    """Sharded serving over four chips, loop and spmd mode, vs exact SpMM."""
    import jax
    import numpy as np

    from repro.kernels import ref
    from repro.serving import GNNServer
    from repro.tuning import PlanCache

    if jax.device_count() != 4:
        raise AssertionError(f"--four-chips needs 4 devices, "
                             f"found {jax.device_count()}")
    csr, x = ds.gcn_adj, ds.features
    log(f"data: {ds.spec.name} nodes={csr.num_rows} edges={int(csr.nnz)} "
        f"F={x.shape[1]} device_bytes={device_bytes(ds)}")
    # No-truncation knobs (as ``repro.serving.server --smoke``): every
    # candidate keeps all edges, so both modes must match the exact SpMM.
    # At arxiv's skew (max row nnz 20,762 against a mean of 14) a block
    # pads to its widest row, so blocks are 64 rows, not 4096: 53 M slots
    # in all, not 629 M.  No plan or bucket timing: nothing is ranked here.
    w_full = int(np.asarray(csr.row_nnz()).max())
    tk = dict(widths=(w_full,), include_full=True, block_rows=64,
              measure_plan=False, measure_buckets=False, warmup=0, iters=1)
    want = ref.csr_spmm(csr.row_ptr, csr.col_ind, csr.val, x)
    cache = PlanCache()     # both modes serve the same per-shard plans
    for mode in ("loop", "spmd"):
        t0 = time.perf_counter()
        server = GNNServer(csr, x, num_shards=4, mode=mode, cache=cache,
                           tune_kwargs=tk)
        try:
            built = time.perf_counter() - t0
            if mode == "loop":
                devs = {d for p in server.plans
                        for d in p.bell.val.devices()}
            else:
                devs = {d for a in server._bundle.bucket_args[0]
                        for d in a.devices()}
            log(f"  {mode}: shard operands on {len(devs)} devices: "
                f"{sorted(d.id for d in devs)}; widths "
                f"{sorted({w for p in server.plans for w in p.bell.widths})}")
            if len(devs) != 4:
                raise AssertionError(f"{mode}: shards not on 4 devices")
            t0 = time.perf_counter()
            got = server.aggregate()
            check_close(f"{mode} vs exact csr_spmm", got, want,
                        atol=1e-5, rtol=1e-5)
            log(f"  {mode}: halo={server.halo_stats()['halo_expansion']!r} "
                f"(smoke timing, not a benchmark metric: build "
                f"{built:.1f}s incl. tuning, first aggregate "
                f"{time.perf_counter() - t0:.1f}s incl. compile)")
        finally:
            server.close()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip sharded serving phase")
    args = p.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: the repro package (src/repro) is missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # everything is built from the checkout: no plan, calibration log or
    # trace sink left on disk by an earlier run
    os.environ.pop("REPRO_PLAN_CACHE_DIR", None)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke.py: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    from repro import obs
    from repro.compile_cache import enable_compile_cache
    from repro.gnn import make_dataset

    log(f"compile cache: {enable_compile_cache()}")
    obs.set_enabled(True)
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")

    ds = make_dataset("ogbn-arxiv", scale=1.0, max_avg_degree=None,
                      seed=SEED)
    if args.four_chips:
        four_chips(ds)
    else:
        one_chip(ds)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
