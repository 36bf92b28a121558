"""Benchmark driver: one module per paper table/figure.  Prints
``name,us_per_call,derived`` CSV rows (plus roofline rows when the dry-run
artifacts exist)."""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (accuracy_vs_w, autotune_gain, block_tuning_gain,
                            calibration_gain, fused_layer, incremental_update,
                            kernel_blocks, kernel_speedup, motivation,
                            quant_block_gain, quant_loading, reorder_gain,
                            sampling_cdf, serving_throughput)

    print("name,us_per_call,derived")
    sampling_cdf.run()
    accuracy_vs_w.run()
    kernel_speedup.run()
    quant_loading.run()
    motivation.run()
    kernel_blocks.run()
    autotune_gain.run()
    block_tuning_gain.run()
    quant_block_gain.run()
    calibration_gain.run()
    # includes the open-loop continuous-batching sweep (ServingRuntime
    # vs synchronous flush under Poisson arrivals -> BENCH_serving.json)
    serving_throughput.run()
    # plan patching vs cold re-tune for a 1% edge delta
    # (-> BENCH_incremental.json, gate: parity + >10x)
    incremental_update.run()
    # fused layer kernel vs unfused 2-layer GCN
    # (-> BENCH_fused.json, gate: parity + speedup>1 + bytes win)
    fused_layer.run()
    # degree-sorted vs natural row layout: padded-slot budget + bit parity
    # (-> BENCH_reorder.json, gate: parity + slots>=1.5x + auto picks)
    reorder_gain.run()
    try:
        from benchmarks import roofline
        roofline.report()
    except (ImportError, FileNotFoundError) as e:
        print(f"roofline/skipped,0.0,reason={type(e).__name__}", file=sys.stderr)


if __name__ == "__main__":
    main()
