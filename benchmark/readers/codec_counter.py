"""A codec counter's change over the window, per unit of work done:
per GB of .dat behind the completed verbs ("gb") or per successful read
("read").  seaweedfs_codec_op_seconds runs from a call's issue to its
fetch and calls overlap in the encoder's pipeline, so its sum is host
wait time, not device time."""

from .. import core


def read(w, trace, devices, series: str, op: str, per: str,
         scale: float = 1.0):
    if per == "gb":
        base = sum(v["bytes"] for v in w.verbs if v.get("complete")) / 1e9
    elif per == "read":
        base = sum(r["ok"] for r in w.reads)
    else:
        raise ValueError(f"unknown base {per!r}")
    if not base:
        return None
    return scale * core.counter_delta(w, series, op=op) / base
