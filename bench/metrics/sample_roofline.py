"""The AES sampling kernel's share of its roofline, in %.

Layer: sampling (``kernels/aes_sample.py``).  Least time of a forward's
sampler calls over their measured device time (``sample_ms``).  The
least bytes of one call (:func:`sample_bytes`) are a true lower bound
over the work counts' nodes and sampled edges; each call's width W is
read from the shape of its output in the trace (the op's HLO text,
``%aes_sample.1 = (f32[rows,W], s32[rows,W]) custom-call(...)``, which
``tests/test_tpu_compile.py`` checks in the v5e HLO).
"""
import re

from bench.graphs import least_time, share
from bench.tracing import is_pallas, op_name

KERNELS = ("aes_sample",)
OUTPUT = re.compile(r" = \(f32\[\d+,(\d+)\]")


def sample_bytes(nodes: int, sampled_edges: int, sh_width: int) -> int:
    """Least bytes of one AES sampling call over ``nodes`` rows: ``row_ptr``
    read once (``4 (n + 1)``), the f32 value and int32 column of each live
    slot read once, and the ``[n, W]`` ELL value and column written once.
    No CSR entry that the sample leaves out is counted."""
    return 4 * (nodes + 1) + 8 * sampled_edges + 8 * nodes * sh_width


def read(r):
    ms = r.metric("sample_ms")
    if ms is None:
        return None
    nbytes = 0
    for ev in r.trace.ops:
        if not (is_pallas(ev) and op_name(ev) in KERNELS):
            continue
        shape = OUTPUT.search(ev.name)
        if shape is None:
            return None
        nbytes += sample_bytes(r.work["nodes"], r.work["sampled_edges"],
                               int(shape.group(1)))
    least = least_time(0, nbytes / r.trace.devices / r.forwards, r.peak)
    return share(least, ms * 1e-3)
