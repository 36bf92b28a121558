"""The SpMM kernels' share of their roofline, in %.

Least time of a forward's two sampled SpMMs (``graphs.spmm_bytes``: B and
the sampled operand read once, C written once; ``graphs.spmm_flops``)
over their measured device time (``spmm_ms``).
"""
from bench.graphs import least_time, share


def read(r):
    ms = r.metric("spmm_ms")
    if ms is None:
        return None
    least = least_time(r.work["spmm_flops"], r.work["spmm_bytes"], r.peak)
    return share(least, ms * 1e-3)
