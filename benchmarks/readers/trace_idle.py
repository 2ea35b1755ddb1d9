"""The device's idle share of the traced window: 1 - union of the
device-operation intervals over the window, averaged over the chips."""

from . import xplane


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - xplane.busy_seconds(rec.trace)
                    / rec.trace.window_s)
