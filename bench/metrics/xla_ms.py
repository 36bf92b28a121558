"""Device time of every op that is not a Pallas kernel, per forward, in
ms: the dense transforms and the layout, padding and sentinel work in
the forward and the ops wrappers.

Layer: dense transform and layout (XLA).
"""
from bench.tracing import is_pallas


def read(r):
    s = r.trace.seconds(lambda ev: not is_pallas(ev))
    return 1e3 * s / r.forwards if s > 0 else None
