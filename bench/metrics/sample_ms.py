"""Device time of the AES sampling kernel per forward, in ms.

Layer: sampling (``core/aes_spmm.sample``, ``kernels/aes_sample.py``).
The kernel is the Pallas call that the jitted ``aes_sample`` wrapper
makes; the trace names it after that wrapper.
"""
from bench.tracing import is_pallas, op_name

KERNELS = ("aes_sample",)


def read(r):
    s = r.trace.seconds(lambda ev: is_pallas(ev) and op_name(ev) in KERNELS)
    return 1e3 * s / r.forwards if s > 0 else None
