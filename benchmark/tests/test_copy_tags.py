"""readers/copy_tags.py on hand-made spans: the sums, the two bases, the
verbs it leaves out, and the spans of a program that carries no tags."""

import pytest

from benchmark import core
from benchmark.readers import copy_tags

C = "VolumeServer/CopyFile"
E = "VolumeServer/VolumeEcShardsCopy"
BOTH = [C, E]


def _window():
    verbs = [{"tid": "a", "seconds": 4.0, "bytes": 2e9, "complete": True},
             {"tid": "b", "seconds": 4.0, "bytes": 2e9, "complete": True},
             {"tid": "x", "seconds": 9.0, "bytes": 2e9, "complete": False}]
    spans = [
        {"trace_id": "a", "name": C, "read_s": 0.5, "frame_s": 1.0,
         "bytes": 1e9},
        {"trace_id": "a", "name": E, "recv_s": 2.0, "frame_s": 0.5,
         "write_s": 0.25, "bytes": 1e9},
        {"trace_id": "b", "name": C, "read_s": 0.5, "frame_s": 1.0,
         "bytes": 1e9},
        {"trace_id": "b", "name": E, "recv_s": 2.0, "frame_s": 0.5,
         "write_s": 0.25, "bytes": 1e9},
        # an error span (a missing .ecj) carries no bytes
        {"trace_id": "b", "name": C, "status": "error"},
        # another RPC's framing and an incomplete verb's copy: not read
        {"trace_id": "a", "name": "VolumeServer/VolumeEcShardsGenerate",
         "frame_s": 99.0},
        {"trace_id": "x", "name": E, "frame_s": 99.0, "bytes": 9e9},
        {"trace_id": "z", "name": E, "frame_s": 99.0, "bytes": 9e9}]
    return core.Window(verbs=verbs, spans=spans)


def test_wire_and_disk_seconds_per_gb_copied():
    w = _window()
    # 2 GB received by the copies; frame 3.0 s, disk 1.5 s over both ends
    assert copy_tags.read(w, None, [], tags=["frame_s"], spans=BOTH,
                          per="copied_gb") == pytest.approx(1.5)
    assert copy_tags.read(w, None, [], tags=["read_s", "write_s"],
                          spans=BOTH, per="copied_gb") \
        == pytest.approx(0.75)
    assert copy_tags.read(w, None, [], tags=["recv_s"], spans=[E],
                          per="copied_gb") == pytest.approx(2.0)


def test_gb_copied_per_gb_sealed():
    w = _window()
    assert copy_tags.read(w, None, [], tags=["bytes"], spans=[E],
                          per="gb", scale=1e-9) == pytest.approx(0.5)


def test_untagged_spans_read_as_nothing():
    w = _window()
    for s in w.spans:
        for k in ("read_s", "frame_s", "write_s", "recv_s", "bytes"):
            s.pop(k, None)
    assert copy_tags.read(w, None, [], tags=["frame_s"], spans=BOTH,
                          per="copied_gb") is None
    assert copy_tags.read(w, None, [], tags=["bytes"], spans=[E],
                          per="gb", scale=1e-9) is None
    assert copy_tags.read(core.Window(), None, [], tags=["frame_s"],
                          spans=BOTH, per="gb") is None


def test_unknown_base_is_an_error():
    with pytest.raises(ValueError):
        copy_tags.read(_window(), None, [], tags=["frame_s"], spans=BOTH,
                       per="verb")
