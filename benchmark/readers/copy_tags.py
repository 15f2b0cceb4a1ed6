"""The shard copy's stage tags, summed over the program's spans of the
window's completed verbs: `tags` (e.g. frame_s, read_s + write_s, or
bytes) on the spans named in `spans`, per GB the copies received
("copied_gb": the `bytes` tag of VolumeEcShardsCopy) or per GB of .dat
behind the verbs ("gb").  A program whose spans carry none of the tags
reads as nothing."""

COPY = "VolumeServer/VolumeEcShardsCopy"


def read(w, trace, devices, tags: list, spans: list, per: str,
         scale: float = 1.0):
    done = [v for v in w.verbs if v.get("complete")]
    tids = {v["tid"] for v in done}
    mine = [s for s in w.spans if s["trace_id"] in tids]
    if per == "copied_gb":
        base = sum(s.get("bytes", 0) for s in mine if s["name"] == COPY)
    elif per == "gb":
        base = sum(v["bytes"] for v in done)
    else:
        raise ValueError(f"unknown base {per!r}")
    values = [s[t] for s in mine if s["name"] in spans
              for t in tags if t in s]
    if not base or not values:
        return None
    return scale * sum(values) / (base / 1e9)
