"""Fused clay VMEM kernel == XLA structured == flat generator == numpy oracle,
byte for byte — encode AND single-loss repair, across geometries,
window widths and loss masks.

The fused kernels (rs_pallas._clay_fused_encode_kernel / _repair_kernel)
are the production TPU hot path; on this CPU suite they run through the
Pallas interpreter (JAX's backend is not a TPU), so tier-1 proves the
kernel's own math — uncouple, layer-MDS bit-plane matmul, couple, the
virtual-zero-row synthesis and the out-of-plane back-substitution —
without a chip.  Any divergence is data corruption: np.array_equal
everywhere."""

import os

import numpy as np
import pytest

from clay_oracle import natural_layout_parity
from seaweedfs_tpu.ops import clay_matrix, clay_structured, gf256

GEOMETRIES = [(4, 2), (6, 3), (10, 4)]


def _interpret(monkeypatch):
    """Open the fused gate on this CPU host (the kernels then run through
    the Pallas interpreter) and make the gates deterministic regardless
    of the outer WEED_EC_BACKEND arm (tools/check.sh runs this file
    twice).  device_compute_ok is pinned True so the device branches run
    on this CPU host — the standing idiom from test_clay_structured.py."""
    import seaweedfs_tpu.ops.codec as codec_mod
    monkeypatch.setattr(clay_structured, "use_fused_engine", lambda: True)
    monkeypatch.delenv("WEED_EC_BACKEND", raising=False)
    monkeypatch.setattr(codec_mod, "device_compute_ok", lambda: True)


def _gate_off(monkeypatch):
    monkeypatch.setattr(clay_structured, "use_fused_engine", lambda: False)


# -- encode -----------------------------------------------------------------

@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_fused_encode_bit_identity(k, m, monkeypatch):
    """fused == XLA structured (gate off) == flat generator == oracle."""
    import jax.numpy as jnp
    c = clay_matrix.code(k, m)
    small = c.alpha * 128
    n_win = 2
    W = n_win * small
    rng = np.random.default_rng(k * 100 + m)
    data = rng.integers(0, 256, (k, W), dtype=np.uint8)
    oracle = natural_layout_parity(k, m, data, small)
    shape4 = clay_structured.fused_shape(k, m, W, small)
    assert shape4 == (k, n_win, c.alpha, 128)
    fused = np.asarray(clay_structured.encode_device_fused(
        k, m, jnp.asarray(data.reshape(shape4)), small=small)
    ).reshape(m, W)
    assert np.array_equal(fused, oracle)
    _gate_off(monkeypatch)
    xla = np.asarray(clay_structured.encode_device(
        k, m, jnp.asarray(data), small=small))
    assert np.array_equal(fused, xla)
    win_a = small // c.alpha
    flat_in = np.ascontiguousarray(
        data.reshape(k, n_win, c.alpha, win_a).transpose(0, 2, 1, 3)
    ).reshape(k * c.alpha, -1)
    flat = gf256.matmul(clay_matrix.generator_flat(k, m), flat_in)
    flat = np.ascontiguousarray(
        flat.reshape(m, c.alpha, n_win, win_a).transpose(0, 2, 1, 3)
    ).reshape(m, W)
    assert np.array_equal(fused, flat)


def test_fused_encode_wide_window_cb(monkeypatch):
    """Wider windows exercise the cb column-tile picker (> one 128-lane
    tile per grid step) and multi-window grids."""
    import jax.numpy as jnp
    _interpret(monkeypatch)
    k, m = 4, 2
    c = clay_matrix.code(k, m)
    small = c.alpha * 512           # w_a = 512 -> cb grows past 128
    n_win = 3
    W = n_win * small
    assert clay_structured.rs_pallas.clay_fused_cb_for(c.alpha, 512) > 128 \
        if hasattr(clay_structured, "rs_pallas") else True
    rng = np.random.default_rng(42)
    data = rng.integers(0, 256, (k, W), dtype=np.uint8)
    shape4 = clay_structured.fused_shape(k, m, W, small)
    fused = np.asarray(clay_structured.encode_device_fused(
        k, m, jnp.asarray(data.reshape(shape4)), small=small)
    ).reshape(m, W)
    assert np.array_equal(fused, natural_layout_parity(k, m, data, small))


def test_fused_shape_gates_narrow_windows():
    k, m = 10, 4
    c = clay_matrix.code(k, m)
    assert clay_structured.fused_shape(k, m, c.alpha * 16 * 4,
                                       c.alpha * 16) is None
    assert clay_structured.fused_shape(k, m, c.alpha * 128 * 2,
                                       c.alpha * 128) \
        == (k, 2, c.alpha, 128)


@pytest.mark.parametrize("tpu,pin,want", [
    (True, None, True),        # TPU present
    (False, None, False),      # TPU absent
    (True, "jax", False),      # TPU present, XLA engine pinned
], ids=["tpu", "no-tpu", "jax-pin"])
def test_fused_gate_follows_pallas_engine(tpu, pin, want, monkeypatch):
    """The fused gate is _use_pallas_engine(): no env var of its own."""
    import seaweedfs_tpu.ops.codec as codec_mod
    monkeypatch.setattr(codec_mod, "_tpu_available", lambda: tpu)
    if pin is None:
        monkeypatch.delenv("WEED_EC_BACKEND", raising=False)
    else:
        monkeypatch.setenv("WEED_EC_BACKEND", pin)
    assert clay_structured._use_pallas_engine() is want
    assert clay_structured.use_fused_engine() is want


def test_fused_fallback_matches_tiled(monkeypatch):
    """With the fused gate off, encode_device must take its XLA path on
    windows the fused kernel would accept (the CPU/shard_map contract:
    the fused entry is never called) and still return oracle bytes —
    the same bytes the fused kernel gives."""
    import jax.numpy as jnp
    _gate_off(monkeypatch)

    def no_fused(*a, **kw):
        raise AssertionError("fused kernel called with the gate off")
    k, m = 4, 2
    c = clay_matrix.code(k, m)
    small = c.alpha * 128
    W = 2 * small
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (k, W), dtype=np.uint8)
    shape4 = clay_structured.fused_shape(k, m, W, small)
    fused = np.asarray(clay_structured.encode_device_fused(
        k, m, jnp.asarray(data.reshape(shape4)), small=small)
    ).reshape(m, W)
    monkeypatch.setattr(clay_structured, "encode_device_fused", no_fused)
    out = np.asarray(clay_structured.encode_device(
        k, m, jnp.asarray(data), small=small))
    assert np.array_equal(out, natural_layout_parity(k, m, data, small))
    assert np.array_equal(out, fused)


# -- single-loss repair -----------------------------------------------------

def _encoded_stripe(k, m, small, n_win, seed):
    c = clay_matrix.code(k, m)
    W = n_win * small
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (k, W), dtype=np.uint8)
    parity = natural_layout_parity(k, m, data, small)
    shards = np.concatenate([data, parity])
    return shards.reshape(k + m, n_win, c.alpha, small // c.alpha)


def _fused_repair(k, m, lost, sh4):
    import jax.numpy as jnp
    helpers, plane, _, _ = clay_structured.repair_parts(k, m, lost)
    x4 = np.ascontiguousarray(sh4[list(helpers)][:, :, list(plane)])
    return np.asarray(clay_structured.repair_device_fused(
        k, m, lost, jnp.asarray(x4)))


@pytest.mark.parametrize("k,m", [(4, 2), (6, 3)])
def test_fused_repair_every_single_loss(k, m, monkeypatch):
    """Every lost node: the fused repair returns the lost shard's exact
    bytes from only the helpers' beta repair-plane layers."""
    _interpret(monkeypatch)
    sh4 = _encoded_stripe(k, m, clay_matrix.code(k, m).alpha * 128, 2,
                          seed=k * 10 + m)
    for lost in range(k + m):
        rec = _fused_repair(k, m, lost, sh4)
        assert np.array_equal(rec, sh4[lost]), f"lost={lost}"


def test_fused_repair_default_geometry_sampled(monkeypatch):
    """(10, 4): data, the partial-grid-row node, and parity losses (the
    full sweep lives in the smaller geometries above — each loss is its
    own kernel trace, and interpret-mode traces dominate runtime)."""
    _interpret(monkeypatch)
    k, m = 10, 4
    sh4 = _encoded_stripe(k, m, clay_matrix.code(k, m).alpha * 128, 2,
                          seed=3)
    for lost in (0, 5, 9, 10, 13):
        rec = _fused_repair(k, m, lost, sh4)
        assert np.array_equal(rec, sh4[lost]), f"lost={lost}"


@pytest.mark.parametrize("k,m", GEOMETRIES)
def test_repair_parts_matches_repair_flat_plan(k, m):
    """The fused repair's static plan (helpers order, plane layer order)
    must be the one rebuild_clay's partial-range reads use
    (clay_matrix.repair_flat) — the rebuild driver feeds repair_flat's
    gather straight into the fused kernel."""
    for lost in range(k + m):
        helpers_f, plane_f, _ = clay_matrix.repair_flat(k, m, lost)
        helpers_s, plane_s, R_r, inv_g = clay_structured.repair_parts(
            k, m, lost)
        assert tuple(helpers_f) == helpers_s
        assert tuple(plane_f) == plane_s
        c = clay_matrix.code(k, m)
        assert R_r.shape == (c.q, c.k0)
        assert gf256.mul(np.uint8(inv_g),
                         np.uint8(clay_structured.GAMMA)) == 1


# -- rebuild drivers end to end --------------------------------------------

def _write_clay_volume(tmp_path, name, geo, payload):
    import seaweedfs_tpu.storage.ec as ec
    d = tmp_path / name
    d.mkdir()
    base = str(d / "1")
    with open(base + ".dat", "wb") as f:
        f.write(payload)
    ec.write_ec_files(base, geo)
    return base


def test_rebuild_clay_fused_branch(tmp_path, monkeypatch):
    """rebuild_ec_files with the fused gate open (interpreted on this
    host) runs the fused single-loss branch end to end (memmap plane gather ->
    pallas_call -> shard write) and regenerates byte-identical shards."""
    import seaweedfs_tpu.storage.ec as ec
    c = clay_matrix.code(10, 4)
    geo = ec.EcGeometry(10, 4, large_block_size=1 << 20,
                        small_block_size=c.alpha * 128, code_kind="clay")
    rng = np.random.default_rng(9)
    payload = rng.integers(0, 256, 2 * geo.small_row_size() + 777,
                           dtype=np.uint8).tobytes()
    base = _write_clay_volume(tmp_path, "v", geo, payload)
    want = open(base + ".ec03", "rb").read()
    os.remove(base + ".ec03")
    _interpret(monkeypatch)
    stats = {}
    ec.rebuild_ec_files(base, geo, stats=stats)
    assert stats["plan_kind"] == "clay-plane-fused"
    assert open(base + ".ec03", "rb").read() == want
    # a parity loss exercises the couple-row solve
    want_p = open(base + ".ec12", "rb").read()
    os.remove(base + ".ec12")
    ec.rebuild_ec_files(base, geo)
    assert open(base + ".ec12", "rb").read() == want_p


def test_rebuild_clay_double_loss_masks(tmp_path, monkeypatch):
    """Every double-loss mask on (4, 2) (the multi-loss decode path must
    coexist with the fused gates), sampled masks on (10, 4)."""
    import itertools

    import seaweedfs_tpu.storage.ec as ec
    _interpret(monkeypatch)
    for (k, m), masks in [
        ((4, 2), list(itertools.combinations(range(6), 2))),
        ((10, 4), [(0, 1), (3, 12), (10, 13)]),
    ]:
        c = clay_matrix.code(k, m)
        geo = ec.EcGeometry(k, m, large_block_size=1 << 20,
                            small_block_size=c.alpha * 128,
                            code_kind="clay")
        rng = np.random.default_rng(k + m)
        payload = rng.integers(0, 256, geo.small_row_size() + 123,
                               dtype=np.uint8).tobytes()
        base = _write_clay_volume(tmp_path, f"d{k}_{m}", geo, payload)
        want = {i: open(base + ec.to_ext(i), "rb").read()
                for i in range(k + m)}
        for mask in masks:
            for i in mask:
                os.remove(base + ec.to_ext(i))
            stats = {}
            ec.rebuild_ec_files(base, geo, stats=stats)
            assert stats["plan_kind"] == "clay-decode"
            for i in mask:
                got = open(base + ec.to_ext(i), "rb").read()
                assert got == want[i], f"{(k, m)} mask={mask} shard={i}"


# -- batched fleet encode ---------------------------------------------------

def test_encode_batch_amortization_rs(tmp_path):
    """A 100+-volume RS fleet encodes with measurably fewer dispatches
    than volumes (the amortization counter the /metrics families
    expose), byte-identical to per-volume write_ec_files."""
    import seaweedfs_tpu.storage.ec as ec
    from seaweedfs_tpu.ops.codec import RSCodec, codec_metrics
    geo = ec.EcGeometry(10, 4, large_block_size=1 << 20,
                        small_block_size=4096)
    rng = np.random.default_rng(21)
    n_vol = 104
    bases = []
    for v in range(n_vol):
        d = tmp_path / f"rs{v}"
        d.mkdir()
        base = str(d / "1")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, 3 * geo.small_row_size(),
                                 dtype=np.uint8).tobytes())
        bases.append(base)
    codec = RSCodec(10, 4)
    label = f"rs_{codec.backend}"
    mets = codec_metrics()
    d0 = mets.dispatch.value(label, "encode")
    v0 = mets.dispatch_volumes.value(label, "encode")
    ec.encode_ec_files_batch(bases, geo, codec=codec,
                             batch_bytes=1 << 20)
    dispatches = mets.dispatch.value(label, "encode") - d0
    volumes = mets.dispatch_volumes.value(label, "encode") - v0
    assert 0 < dispatches < n_vol, dispatches
    assert volumes >= n_vol          # every volume rode some dispatch
    assert volumes / dispatches > 10  # real amortization, not off-by-one
    # byte-identity spot check vs the per-volume writer
    ref = str(tmp_path / "ref")
    for base in bases[:3]:
        os.link(base + ".dat", ref + ".dat")
        ec.write_ec_files(ref, geo, codec=codec)
        for i in range(geo.total_shards):
            assert open(base + ec.to_ext(i), "rb").read() \
                == open(ref + ec.to_ext(i), "rb").read()
            os.unlink(ref + ec.to_ext(i))
        os.unlink(ref + ".dat")


def test_encode_batch_clay_window_codec(tmp_path):
    """Clay volumes fold onto the byte axis ([k, V*width]) — the window
    transform is window-local, so the grouped encode must be
    byte-identical to per-volume encodes, and the 'clay' dispatch
    counter must amortize."""
    import seaweedfs_tpu.storage.ec as ec
    from seaweedfs_tpu.ops.codec import codec_metrics
    c = clay_matrix.code(4, 2)
    geo = ec.EcGeometry(4, 2, large_block_size=1 << 20,
                        small_block_size=c.alpha * 128, code_kind="clay")
    rng = np.random.default_rng(31)
    bases = []
    for v in range(6):
        d = tmp_path / f"cl{v}"
        d.mkdir()
        base = str(d / "1")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, 2 * geo.small_row_size() + v,
                                 dtype=np.uint8).tobytes())
        bases.append(base)
    mets = codec_metrics()
    d0 = mets.dispatch.value("clay", "encode")
    v0 = mets.dispatch_volumes.value("clay", "encode")
    ec.encode_ec_files_batch(bases, geo, batch_bytes=1 << 20)
    dispatches = mets.dispatch.value("clay", "encode") - d0
    volumes = mets.dispatch_volumes.value("clay", "encode") - v0
    assert 0 < dispatches < len(bases)
    assert volumes >= len(bases)
    ref = str(tmp_path / "ref")
    for base in bases:
        os.link(base + ".dat", ref + ".dat")
        ec.write_ec_files(ref, geo)
        for i in range(geo.total_shards):
            assert open(base + ec.to_ext(i), "rb").read() \
                == open(ref + ec.to_ext(i), "rb").read()
            os.unlink(ref + ec.to_ext(i))
        os.unlink(ref + ".dat")


def test_encode_batch_odd_sizes_degrade(tmp_path):
    """Volumes with distinct shard sizes land in singleton groups and
    take the per-volume writer — same shard bytes, no lockstep hazard."""
    import seaweedfs_tpu.storage.ec as ec
    geo = ec.EcGeometry(10, 4, large_block_size=1 << 20,
                        small_block_size=4096)
    rng = np.random.default_rng(5)
    bases = []
    for v, rows in enumerate([1, 3]):
        d = tmp_path / f"odd{v}"
        d.mkdir()
        base = str(d / "1")
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, rows * geo.small_row_size(),
                                 dtype=np.uint8).tobytes())
        bases.append(base)
    ec.encode_ec_files_batch(bases, geo, batch_bytes=1 << 20)
    for base in bases:
        ref = base + "_ref"
        os.link(base + ".dat", ref + ".dat")
        ec.write_ec_files(ref, geo)
        for i in range(geo.total_shards):
            assert open(base + ec.to_ext(i), "rb").read() \
                == open(ref + ec.to_ext(i), "rb").read()


# -- observability + pickers ------------------------------------------------

def test_dispatch_counters_unit():
    from seaweedfs_tpu.ops.codec import codec_metrics, metered_fetch
    mets = codec_metrics()
    d0 = mets.dispatch.value("rs_numpy", "encode")
    v0 = mets.dispatch_volumes.value("rs_numpy", "encode")
    metered_fetch(lambda: None, "rs_numpy", "encode", 128, 0.0,
                  volumes=7)()
    assert mets.dispatch.value("rs_numpy", "encode") == d0 + 1
    assert mets.dispatch_volumes.value("rs_numpy", "encode") == v0 + 7
    # the families render at /metrics with the bounded (backend, op) set
    text = mets.registry.render()
    assert "seaweedfs_codec_dispatch_total" in text
    assert "seaweedfs_codec_dispatch_volumes_total" in text


def test_rscodec_counts_batched_volumes():
    from seaweedfs_tpu.ops.codec import RSCodec, codec_metrics
    codec = RSCodec(4, 2, backend="numpy")
    mets = codec_metrics()
    d0 = mets.dispatch.value("rs_numpy", "encode")
    v0 = mets.dispatch_volumes.value("rs_numpy", "encode")
    data = np.zeros((5, 4, 256), dtype=np.uint8)
    codec.encode(data)
    assert mets.dispatch.value("rs_numpy", "encode") == d0 + 1
    assert mets.dispatch_volumes.value("rs_numpy", "encode") == v0 + 5


def test_block_pickers_geometry_aware():
    from seaweedfs_tpu.ops import rs_pallas
    # default geometries keep their swept tiles — no behavior change
    assert rs_pallas.sm_block_b_for(10, 4) == rs_pallas.SM_DEFAULT_BLOCK_B
    assert rs_pallas.sm_block_b_for(16, 8) == rs_pallas.SM_DEFAULT_BLOCK_B
    # wide stripes shrink to hold the VMEM working set constant
    wide = rs_pallas.sm_block_b_for(28, 4)
    assert 128 <= wide < rs_pallas.SM_DEFAULT_BLOCK_B
    assert wide & (wide - 1) == 0      # power of two (tile alignment)
    # RSCodec's default block follows the picker
    from seaweedfs_tpu.ops.codec import RSCodec
    assert RSCodec(28, 4, backend="numpy").block_b == wide
    assert RSCodec(10, 4, backend="numpy").block_b \
        == rs_pallas.SM_DEFAULT_BLOCK_B


def test_fused_cb_picker():
    from seaweedfs_tpu.ops import rs_pallas
    assert rs_pallas.clay_fused_cb_for(256, 128) == 128
    # alpha=256: cb grows only while alpha*cb <= 32768
    assert rs_pallas.clay_fused_cb_for(256, 1024) == 128
    # small alphas amortize the grid with wider tiles
    assert rs_pallas.clay_fused_cb_for(8, 1024) == 1024
    cb = rs_pallas.clay_fused_cb_for(8, 4096)
    assert cb <= 4096 and 4096 % cb == 0
