"""The whole forward's share of the chip's peak, in %.

Least time of a forward (``graphs.forward_bytes`` and the model's FLOPs,
whichever bound is larger) over the traced window per forward, idle time
included, so that no kernel's gain can show here unless it shows end to
end.
"""
from bench.graphs import least_time, share


def read(r):
    least = least_time(r.work["fwd_flops"], r.work["fwd_bytes"], r.peak)
    return share(least, r.trace.window_s / r.forwards)
