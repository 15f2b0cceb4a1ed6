"""LRC code tests: locality of single-shard repair, exhaustive failure
sweeps on small geometries, repair bandwidth accounting."""

import itertools

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.lrc import (LrcGeometry, encode_shards,
                                   generator_matrix, plan_repair, repair)


def make_shards(geo, seed=0, B=256):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (geo.k, B), dtype=np.uint8)
    return data, encode_shards(geo, data)


def test_generator_shape_and_locals():
    geo = LrcGeometry(k=12, l=2, r=2)
    G = generator_matrix(geo)
    assert G.shape == (16, 12)
    # local parity rows are group XOR masks
    assert G[12].tolist() == [1] * 6 + [0] * 6
    assert G[13].tolist() == [0] * 6 + [1] * 6


def test_single_data_failure_repairs_locally():
    geo = LrcGeometry(k=12, l=2, r=2)
    data, shards = make_shards(geo)
    for lost in (0, 5, 7, 11):
        plan = plan_repair(geo, [lost])
        assert plan.kind == "local"
        # locality win: k/l reads instead of k
        assert len(plan.read_shards) == geo.group_size
        got = repair(geo, plan, {s: shards[s] for s in plan.read_shards})
        assert np.array_equal(got[lost], shards[lost])


def test_local_parity_failure_repairs_locally():
    geo = LrcGeometry(k=12, l=2, r=2)
    _, shards = make_shards(geo)
    for g in range(geo.l):
        lost = geo.local_parity_index(g)
        plan = plan_repair(geo, [lost])
        assert plan.kind == "local"
        got = repair(geo, plan, {s: shards[s] for s in plan.read_shards})
        assert np.array_equal(got[lost], shards[lost])


def test_global_parity_failure():
    geo = LrcGeometry(k=12, l=2, r=2)
    _, shards = make_shards(geo)
    lost = geo.k + geo.l  # first global parity
    plan = plan_repair(geo, [lost])
    got = repair(geo, plan, {s: shards[s] for s in plan.read_shards})
    assert np.array_equal(got[lost], shards[lost])


def test_exhaustive_triple_failures_small_geometry():
    """LRC(6,2,2): every 3-failure pattern must be either repaired
    byte-exactly or explicitly reported unrecoverable — never silently
    wrong.  (Azure LRC tolerates all 3-failures and most 4-failures.)"""
    geo = LrcGeometry(k=6, l=2, r=2)
    data, shards = make_shards(geo, seed=3)
    total, recovered = 0, 0
    for missing in itertools.combinations(range(geo.n), 3):
        total += 1
        try:
            plan = plan_repair(geo, list(missing))
        except ValueError:
            continue
        got = repair(geo, plan, {s: shards[s]
                                 for s in plan.read_shards})
        for s in missing:
            assert np.array_equal(got[s], shards[s]), missing
        recovered += 1
    # all triple failures of LRC(6,2,2) are information-theoretically
    # recoverable (n-k = 4 redundancy); the planner must get them all
    assert recovered == total, f"{recovered}/{total}"


def test_double_failure_same_group_uses_global():
    geo = LrcGeometry(k=6, l=2, r=2)
    _, shards = make_shards(geo, seed=4)
    plan = plan_repair(geo, [0, 1])  # two in the same group
    assert plan.kind == "global"
    got = repair(geo, plan, {s: shards[s] for s in plan.read_shards})
    assert np.array_equal(got[0], shards[0])
    assert np.array_equal(got[1], shards[1])


def test_unrecoverable_reported():
    geo = LrcGeometry(k=6, l=2, r=2)
    # 5 failures > n-k=4 redundancy: must raise, not fabricate data
    with pytest.raises(ValueError):
        plan_repair(geo, [0, 1, 2, 3, 4])


def test_repair_bandwidth_advantage():
    """The LRC selling point: single-failure repair reads k/l shards
    vs k for plain RS."""
    geo = LrcGeometry(k=12, l=3, r=2)
    plan = plan_repair(geo, [4])
    assert len(plan.read_shards) == 4   # 12/3 group size
    # RS(12, x) would need 12 reads


# -- Azure LRC(12,2,2): the Maximally Recoverable construction ---------------

AZURE = LrcGeometry(k=12, l=2, r=2)


def _decodable(G, geo, missing):
    from seaweedfs_tpu.ops.lrc import _independent_rows
    avail = [s for s in range(geo.n) if s not in missing]
    return _independent_rows(G, avail, geo.k) is not None


def test_global_rows_are_the_azure_mr_construction():
    """Group g's points are {1..6} << 4g (disjoint bit halves of
    GF(2^8)); global row j holds the points to the power j."""
    G = generator_matrix(AZURE)
    pts = [1, 2, 3, 4, 5, 6, 16, 32, 48, 64, 80, 96]
    assert G[14].tolist() == pts
    assert G[15].tolist() == [int(gf256.mul(p, p)) for p in pts]


def test_exhaustive_loss_sweep_azure_lrc12_2_2():
    """Every 3-loss pattern decodes, and exactly the paper's 86% of the
    4-loss patterns: 1,568 of 1,820.  {0, 1, 7, 10}, which the former
    points 1..12 could not decode (1+2 = 8+11 = 3 in GF(2^8)), is one."""
    G = generator_matrix(AZURE)
    triples = list(itertools.combinations(range(AZURE.n), 3))
    quads = list(itertools.combinations(range(AZURE.n), 4))
    assert sum(_decodable(G, AZURE, m) for m in triples) == len(triples) \
        == 560
    ok = [m for m in quads if _decodable(G, AZURE, m)]
    assert len(quads) == 1820 and len(ok) == 1568
    assert (0, 1, 7, 10) in ok
    old = G.copy()
    old[14] = np.arange(1, 13, dtype=np.uint8)
    old[15] = gf256.gf_pow(old[14], 2)
    assert not _decodable(old, AZURE, (0, 1, 7, 10))


@pytest.mark.parametrize("missing", [(0, 1, 7, 10), (3, 9, 14),
                                     (12, 13, 14, 15), (5, 6, 12, 15)],
                         ids=["paper-4loss", "3loss", "all-parity",
                              "cross-group"])
def test_azure_multi_loss_repairs_byte_exact(missing):
    _, shards = make_shards(AZURE, seed=12, B=4096)
    plan = plan_repair(AZURE, list(missing))
    assert plan.kind == "global"
    got = repair(AZURE, plan, {s: shards[s] for s in plan.read_shards})
    for s in missing:
        assert np.array_equal(got[s], shards[s]), s


def test_construction_refuses_geometries_it_cannot_hold():
    """Three groups leave no disjoint bit field per group in GF(2^8):
    the generator is refused, never built from colliding points; the
    local plan, which reads no global row, still serves."""
    geo = LrcGeometry(k=12, l=3, r=2)
    with pytest.raises(ValueError, match="azure-mr"):
        generator_matrix(geo)
    assert plan_repair(geo, [4]).kind == "local"
