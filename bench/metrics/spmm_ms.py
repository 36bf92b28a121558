"""Device time of the ELL SpMM kernel per forward, in ms.

Layer: SpMM kernels (``kernels/ell_spmm.py`` over ``kernels/gather.py``).
The kernel is the Pallas call that the jitted ``ell_spmm`` wrapper makes;
the trace names it after that wrapper.
"""
from bench.tracing import is_pallas, op_name

KERNELS = ("ell_spmm",)


def read(r):
    s = r.trace.seconds(lambda ev: is_pallas(ev) and op_name(ev) in KERNELS)
    return 1e3 * s / r.forwards if s > 0 else None
