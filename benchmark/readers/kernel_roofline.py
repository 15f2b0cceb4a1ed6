"""A kernel's share of its HBM roofline: the least bytes the arithmetic
must move through HBM for the window's calls, over the chip's HBM
bandwidth, against the summed device time of the kernel's events.

The bytes are counted from what the codec was given (its payload
counter), whatever the kernel does inside: a kernel that works another
way is read against the same work and cannot pass 100%."""

from .. import core, devtrace


def rs_encode_bytes(payload: float, k: int, m: int) -> float:
    """RS encode of k data rows of B bytes: k*B in, m*B out."""
    return payload * (k + m) / k


def clay_repair_bytes(payload: float, helpers: int, beta: int,
                      alpha: int) -> float:
    """Clay single-node repair: the payload is the helpers' plane
    sub-chunks (helpers * beta per column); out come the lost node's
    alpha sub-chunks per column."""
    return payload * (1 + alpha / (helpers * beta))


def read(w, trace, devices, kernel: str, op: str, bytes_fn: str,
         **shape):
    seconds = devtrace.kernel_seconds(trace, kernel)
    payload = core.counter_delta(w, "seaweedfs_codec_bytes_total", op=op)
    if not seconds or not payload:
        return None
    need = globals()[bytes_fn](payload, **shape)
    hbm = core.peak(devices[0].device_kind, "hbm_bytes_per_s")
    return 100.0 * need / hbm / seconds
