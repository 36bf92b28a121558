#!/usr/bin/env python3
"""Time the ``ell_spmm`` gather alone, per lane tile and per DMA depth.

    PYTHONPATH=src python3 scripts/gather_probe.py \\
        [--configs gcn-ogbn-arxiv,graphsage-pubmed,gcn-reddit] \\
        [--tiles 1,2,3,4,5] [--depths 4,8,16,32] [--rows 16384] \\
        [--reps 3] [--seed 0] [--out artifacts/gather_probe.json]

For each benchmark configuration (``bench/configs/<name>.json``) the probe
builds a sampled ELL operand of that configuration's shape: its node count
n and ELL width W, each row's live width ``min(nnz, W)`` from the
configuration's degree sequence (self loops included where the model adds
them), and columns drawn as ``bench/graphs.make_graph`` draws them (in the
row's class with probability ``homophily``, uniform otherwise; sorted
within a row).  It times a random sample of ``--rows`` of those rows, all
of them where the graph has fewer, against a dense f32 operand of n rows
and 1 to 5 lane tiles, for

  * ``loop``: the per-tile grid with two row DMAs in flight, which
    ``ell_spmm`` runs on a one-tile operand;
  * ``ring<D>``: one program per row tile, every tile of a row in one DMA,
    ``D`` DMAs in flight (``ell_spmm.RING_DEPTH`` set to ``D`` while the
    call traces), which ``ell_spmm`` runs on a multi-tile operand.

It prints one line per (configuration, tiles, variant): the median call
time over ``--reps`` calls after a warm-up, and the nanoseconds per
tile-row, the call time over (live edges x tiles).  Every ring variant's
output must equal the loop's bit for bit; a difference is reported and
the probe exits 1.  The lines also go to ``--out`` as JSON.

Run it on the chip; without one the kernels run in interpret mode, which
checks the flow at a small ``--rows`` and says nothing about speed.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

CONFIGS = ("gcn-ogbn-arxiv", "graphsage-pubmed", "gcn-reddit")


def ell_of(config: dict, rows: int, seed: int):
    """The sampled operand of ``rows`` random rows of the configuration's
    graph: ``(val, col, live)`` padded to whole row tiles of 8, and n."""
    from bench import graphs, run

    g = config["graph"]
    n, w = g["nodes"], config["sh_width"]
    model = run.load_module(run.BENCH / "models" / f"{config['model']}.py")
    nnz = graphs.degree_sequence(g) + (model.ADJACENCY == "gcn")
    rng = np.random.default_rng(seed)
    pick = np.sort(rng.choice(n, size=min(rows, n), replace=False))
    live = np.minimum(nnz[pick], w).astype(np.int32)
    comm = graphs.communities(n, g["classes"])
    bounds = np.searchsorted(comm, np.arange(g["classes"] + 1))
    r = pick.shape[0] + (-pick.shape[0]) % 8
    col = np.zeros((r, w), np.int32)
    val = np.zeros((r, w), np.float32)
    for i, (node, k) in enumerate(zip(pick, live)):
        lo, hi = bounds[comm[node]], bounds[comm[node] + 1]
        same = rng.integers(lo, hi, k)
        col[i, :k] = np.sort(np.where(rng.random(k) < g["homophily"], same,
                                      rng.integers(0, n, k)))
        val[i, :k] = rng.random(k, dtype=np.float32)
    return val, col, np.pad(live, (0, r - live.shape[0])), n


def timed(fn, args, reps: int):
    """Median seconds of ``fn(*args)`` over ``reps`` calls after one
    warm-up call, and its output."""
    out = fn(*args).block_until_ready()
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        secs.append(time.perf_counter() - t0)
    return statistics.median(secs), out


def probe(name: str, tiles: list, depths: list, rows: int, reps: int,
          seed: int, interpret: bool, log=print) -> list:
    import jax
    import jax.numpy as jnp

    from bench import run
    from repro.kernels import ell_spmm
    from repro.kernels.gather import LANES, TiledFeatures

    config = run.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    val, col, live, n = ell_of(config, rows, seed)
    edges = int(live.sum())
    args = (jnp.asarray(val), jnp.asarray(col), jnp.asarray(live))
    lines = []
    for t in tiles:
        b = TiledFeatures(jax.random.normal(jax.random.key(seed),
                                            (t, n, LANES), jnp.float32),
                          t * LANES, 1, None)
        want = None
        for depth in [None] + depths:
            if depth is not None:
                ell_spmm.RING_DEPTH = depth
            fn = jax.jit(functools.partial(
                ell_spmm._launch, ring=depth is not None, block_r=8,
                scale=1.0, x_min=0.0, interpret=interpret))
            secs, out = timed(fn, args + (b,), reps)
            if want is None:
                want, equal = out, True
            else:
                equal = bool(jnp.array_equal(
                    jax.lax.bitcast_convert_type(out, jnp.int32),
                    jax.lax.bitcast_convert_type(want, jnp.int32)))
            line = {"config": name, "tiles": t,
                    "variant": "loop" if depth is None else f"ring{depth}",
                    "rows": int(val.shape[0]), "live_edges": edges,
                    "ms": 1e3 * secs,
                    "ns_per_tile_row": 1e9 * secs / max(edges * t, 1),
                    "bit_equal": equal}
            lines.append(line)
            log(f"{name:18s} tiles {t}  {line['variant']:7s} "
                f"{line['ms']:10.3f} ms  {line['ns_per_tile_row']:8.2f} ns"
                f"  {'=' if equal else 'DIFFERS'}", flush=True)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--tiles", default="1,2,3,4,5")
    ap.add_argument("--depths", default="4,8,16,32")
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="artifacts/gather_probe.json")
    args = ap.parse_args(argv)

    import jax

    from repro.kernels import ell_spmm

    interpret = jax.default_backend() != "tpu"
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}"
          + (" (interpret mode: no timing)" if interpret else ""))
    depth = ell_spmm.RING_DEPTH
    lines = []
    try:
        for name in args.configs.split(","):
            lines += probe(name, [int(t) for t in args.tiles.split(",")],
                           [int(d) for d in args.depths.split(",")],
                           args.rows, args.reps, args.seed, interpret)
    finally:
        ell_spmm.RING_DEPTH = depth
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"device": dev.device_kind,
                               "interpret": interpret, "lines": lines},
                              indent=1))
    return 0 if all(line["bit_equal"] for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
