"""Share of the traced window in which no operation ran on the device:
1 - (union of the device op intervals) / window, averaged over chips."""

from .. import devtrace


def read(w, trace, devices):
    if not trace.devices or not w.seconds:
        return None
    return 100.0 * (1.0 - devtrace.mean_busy_seconds(trace) / w.seconds)
