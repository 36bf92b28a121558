"""Share of the traced window in which the chip ran nothing, in %.

Layer: device.  One minus the union of the device's op intervals over the
window (``tracing.reduce``).
"""


def read(r):
    if r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
