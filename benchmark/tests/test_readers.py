"""The reduction from trace, spans and counters to per-layer metrics, on
a trace recorded on a v5e chip (PR 22's probe: three RS encodes of
[10, 8 MiB], one clay fused encode, one clay fused repair, one small RS
reconstruct, each under a "probe.*" host annotation)."""

import os
import types

import pytest

from benchmark import core, devtrace
from benchmark.readers import (codec_counter, device_idle, kernel_roofline,
                               rpc_share)

TRACE = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    root = tmp_path_factory.mktemp("trace")
    prof = root / "plugins" / "profile" / "probe"
    prof.mkdir(parents=True)
    with open(os.path.join(TRACE, "probe.xplane.pb"), "rb") as f:
        (prof / "vm.xplane.pb").write_bytes(f.read())
    return devtrace.load(str(root), host_prefix="probe.")


def test_device_ops(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    names = [n for n, _, _ in trace.devices["/device:TPU:0"]]
    assert names.count("gf_matmul_bits_pallas_sm") == 4
    assert names.count("clay_fused_encode_pallas") == 1
    assert names.count("clay_fused_repair_pallas") == 1
    assert devtrace.kernel_seconds(trace, "gf_matmul_bits_pallas_sm") \
        == pytest.approx((7243801 + 94997) * 1e-9)
    # the ops do not overlap: busy time is their sum
    assert devtrace.mean_busy_seconds(trace) == pytest.approx(
        (7243801 + 4665823 + 785659 + 94997 + 562) * 1e-9)
    top = devtrace.top_ops(trace)
    assert top[0][0] == "gf_matmul_bits_pallas_sm"


def test_idle_gaps_named_by_host_span(trace):
    rs_span = next(x for x in trace.host if x[0] == "probe.rs")
    window = (rs_span[1], rs_span[1] + rs_span[2])
    gaps = devtrace.idle_gaps(trace, window, n=3)
    assert len(gaps) == 3 and all(g[0] == "probe.rs" for g in gaps)
    # three kernels of 2.4 ms in a 210 ms span: the gaps are the rest
    busy = 3 * 2414600e-9
    assert sum(g[1] for g in devtrace.idle_gaps(trace, window, n=10)) \
        == pytest.approx(rs_span[2] - busy, rel=1e-3)


def test_union():
    assert devtrace.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]


def test_op_names():
    assert devtrace.op_name("%gf_matmul_bits_pallas_sm.1 = u8[4,8]{1,0} "
                            "custom-call(s8[32,80])") \
        == "gf_matmul_bits_pallas_sm"
    assert devtrace.op_name("%copy.12 = s8[32,80] copy()") == "copy"


def test_roofline_bytes():
    # RS(10,4) of B-byte rows: 10B in, 4B out
    assert kernel_roofline.rs_encode_bytes(10 * 100, 10, 4) == 1400
    # clay repair: 13 helpers x 64 of 256 layers in, 256 layers out
    assert kernel_roofline.clay_repair_bytes(13 * 64, 13, 64, 256) \
        == 13 * 64 + 256


def _window(**kw):
    w = core.Window(**kw)
    return w


def test_roofline_share_on_recorded_trace(trace):
    payload = 4 * 10 * (8 << 20)         # as if four [10, 8 MiB] calls
    w = _window(codec_before={},
                codec_after={("seaweedfs_codec_bytes_total", "rs_pallas",
                              "encode"): payload})
    dev = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    share = kernel_roofline.read(w, trace, dev, "gf_matmul_bits_pallas_sm",
                                 "encode", "rs_encode_bytes", k=10, m=4)
    want = 100 * payload * 1.4 / 819e9 / ((7243801 + 94997) * 1e-9)
    assert share == pytest.approx(want) and 0 < share < 100
    assert kernel_roofline.read(w, trace, dev, "no_such_kernel", "encode",
                                "rs_encode_bytes", k=10, m=4) is None


def test_device_idle(trace):
    w = _window(seconds=1.0)
    idle = device_idle.read(w, trace, [])
    assert idle == pytest.approx(100 * (1 - 0.012790842))
    assert device_idle.read(w, devtrace.Trace(), []) is None


def test_rpc_share_and_codec_counter():
    verbs = [{"tid": "a", "seconds": 2.0, "bytes": 1e9, "complete": True},
             {"tid": "b", "seconds": 2.0, "bytes": 1e9, "complete": True}]
    spans = [{"trace_id": "a", "name": "VolumeServer/X", "duration_ms": 500},
             {"trace_id": "b", "name": "VolumeServer/X", "duration_ms": 1500},
             {"trace_id": "c", "name": "VolumeServer/X", "duration_ms": 9e9},
             {"trace_id": "a", "name": "VolumeServer/Y", "duration_ms": 9}]
    w = _window(verbs=verbs, spans=spans,
                codec_before={("s_sum", "rs_pallas", "encode"): 1.0},
                codec_after={("s_sum", "rs_pallas", "encode"): 4.0,
                             ("s_sum", "rs_pallas", "reconstruct"): 7.0})
    assert rpc_share.read(w, None, [], span="VolumeServer/X") == 50.0
    assert codec_counter.read(w, None, [], series="s_sum", op="encode",
                              per="gb") == 1.5
    assert rpc_share.read(_window(), None, [], span="X") is None


def test_unknown_device_kind_is_an_error():
    assert core.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        core.peak("TPU v99", "hbm_bytes_per_s")
