"""The RPC wire framing (pb/rpc.py `_ser`/`_de`): a message with top-level
bytes values travels as an envelope of a JSON header and the raw
payloads; every other message stays the exact JSON it always was."""

import json

import pytest

from seaweedfs_tpu.pb.rpc import _de, _ser
from seaweedfs_tpu.util import tracing

MIB = bytes(range(256)) * 4096


@pytest.mark.parametrize("msg, want", [
    ({"file_content": b""}, {"file_content": b""}),
    ({"data": b"\x00"}, {"data": b"\x00"}),
    ({"data": MIB}, {"data": MIB}),
    ({"a": b"first", "b": b"\x00{second}"},
     {"a": b"first", "b": b"\x00{second}"}),
    ({"data": bytearray(b"ba"), "more": memoryview(b"mv")},
     {"data": b"ba", "more": b"mv"}),
    ({"data": memoryview(MIB)[1:-1]}, {"data": MIB[1:-1]}),
    ({"volume_id": 7, "ext": ".ec03", "nested": {"k": [1, "x", None]},
      "data": b"\xff" * 300, "ok": True},
     {"volume_id": 7, "ext": ".ec03", "nested": {"k": [1, "x", None]},
      "data": b"\xff" * 300, "ok": True}),
], ids=["empty", "one_byte", "one_mib", "two_fields", "bytearray_memoryview",
        "memoryview_slice", "beside_nested_json"])
def test_bytes_fields_round_trip_raw(msg, want):
    t = tracing.Tracer("t")
    with t.span("ser"):
        wire = _ser(msg)
    with t.span("de"):
        got = _de(wire)
    assert got == want
    assert all(type(got[k]) is bytes for k, v in want.items()
               if isinstance(v, bytes))
    payload = sum(len(v) for v in want.values() if isinstance(v, bytes))
    # the payloads travel raw, beside a header that is small
    assert wire[:1] == b"\x00"
    assert len(wire) - payload < 200
    ser, de = t.snapshot()
    assert ser["raw_bytes"] == de["raw_bytes"] == payload
    assert ser["frame_s"] > 0 and de["frame_s"] > 0


@pytest.mark.parametrize("msg", [
    {},
    {"volume_id": 3, "ext": ".dat"},
    {"key": "a2V5", "value": "dmFsdWU=", "deep": {"blob": "eA=="}},
    {"volumes": [{"id": 1, "size": 2}], "ip": "127.0.0.1", "x": None},
    {"s": "\x00 and é", "f": 1.5},
], ids=["empty", "request", "b64_strings", "heartbeat_like", "unicode"])
def test_messages_without_bytes_stay_plain_json(msg):
    t = tracing.Tracer("t")
    with t.span("ser"):
        wire = _ser(msg)
    assert wire == json.dumps(msg, separators=(",", ":")).encode()
    assert _de(wire) == msg
    (span,) = t.snapshot()
    assert "raw_bytes" not in span


def test_bytes_nested_below_the_top_level_are_refused():
    with pytest.raises(TypeError):
        _ser({"deep": {"data": b"x"}})


def test_truncated_envelope_is_refused():
    wire = _ser({"data": b"0123456789"})
    with pytest.raises(ValueError):
        _de(wire[:-1])
    with pytest.raises(ValueError):
        _de(wire + b"!")
