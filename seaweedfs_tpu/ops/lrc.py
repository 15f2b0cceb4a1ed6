"""LRC — Locally Repairable Codes (Azure-LRC style), beyond the
reference's fixed RS(10,4).

LRC(k, l, r): k data shards in l local groups (k/l each); each group adds
one LOCAL parity (the GF sum of its group); r GLOBAL parities are powers
of per-column points over all k, chosen as Azure's Maximally Recoverable
construction (generator_matrix).  Shard order: [data 0..k-1 | local
parities k..k+l-1 | global parities k+l..k+l+r-1].

Why it matters for a storage rack: a single lost shard — the overwhelmingly
common failure — rebuilds from its k/l group peers instead of k shards,
cutting rebuild IO/network by l x (for LRC(12,2,2): 6 reads instead of 12).
Multi-failures fall back to a global solve over any invertible k-subset.

The encode is one GF(2^8) matmul, so the same TPU bit-plane kernels serve
it (bit_matrix of the parity rows feeds rs_jax/rs_pallas); the numpy
oracle here is the correctness reference, exactly as with RS.

BASELINE.md lists Clay/LRC regenerating codes as the post-reference
stretch; SURVEY §7 calls the reconstruct planner the novel piece — that is
`plan_repair` below.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from . import gf256


@dataclass(frozen=True)
class LrcGeometry:
    k: int = 12   # data shards
    l: int = 2    # local groups (k % l == 0)
    r: int = 2    # global parities

    @property
    def n(self) -> int:
        return self.k + self.l + self.r

    @property
    def group_size(self) -> int:
        return self.k // self.l

    def group_of(self, data_shard: int) -> int:
        return data_shard // self.group_size

    def group_members(self, g: int) -> list[int]:
        s = self.group_size
        return list(range(g * s, (g + 1) * s))

    def local_parity_index(self, g: int) -> int:
        return self.k + g


# Recorded in every LRC volume's .vif: the rule its global rows follow.
# Volumes without it were sealed under other coefficients and are refused
# by rebuild and degraded reads (storage/ec/codes.require_construction).
CONSTRUCTION = "azure-mr"


def global_points(geo: LrcGeometry) -> list[int]:
    """The evaluation point of each data column: column i of group g gets
    (i + 1) << 4g, so group 0 takes {1..6} in the low nibble and group 1
    {16, 32, ..., 96} in the high one (LRC(12,2,2))."""
    if geo.l > 2 or geo.group_size > 15:
        raise ValueError(
            f"no {CONSTRUCTION} construction for LRC({geo.k},{geo.l},"
            f"{geo.r}): it needs at most 2 local groups of at most 15")
    return [(i + 1) << (4 * g) for g in range(geo.l)
            for i in range(geo.group_size)]


@functools.lru_cache(maxsize=32)
def generator_matrix(geo: LrcGeometry) -> np.ndarray:
    """(n, k) over GF(2^8): identity; l local XOR rows; r global rows.

    The global rows are the Maximally Recoverable construction of Huang
    et al., Erasure Coding in Windows Azure Storage (USENIX ATC 2012),
    section 2.2: global row j (j = 1..r) holds each data column's point
    (global_points) raised to the power j.  The two groups' points live
    in disjoint bit halves of GF(2^8), so no sum of two of one group's
    points equals a sum of two of the other's, which is the paper's
    condition for every information-theoretically decodable loss pattern
    to decode.  For LRC(12,2,2): all 560 3-loss patterns and 1,568 of the
    1,820 4-loss patterns (86%, the paper's figure)."""
    if geo.k % geo.l:
        raise ValueError(f"k={geo.k} not divisible by l={geo.l}")
    G = np.zeros((geo.n, geo.k), dtype=np.uint8)
    G[:geo.k] = gf256.identity(geo.k)
    for g in range(geo.l):
        for c in geo.group_members(g):
            G[geo.local_parity_index(g), c] = 1  # XOR = GF(2^8) add
    pts = np.asarray(global_points(geo), dtype=np.uint8)
    for j in range(geo.r):
        G[geo.k + geo.l + j] = gf256.gf_pow(pts, j + 1)
    G.setflags(write=False)
    return G


def encode(geo: LrcGeometry, data: np.ndarray) -> np.ndarray:
    """data [k, B] -> parities [l + r, B] (locals first)."""
    G = generator_matrix(geo)
    return gf256.matmul(G[geo.k:], data)


@dataclass
class RepairPlan:
    kind: str                  # "local" | "global"
    read_shards: list[int]    # shard ids to read
    matrix: np.ndarray        # [n_missing, len(read_shards)] decode coeffs
    missing: list[int]


def plan_repair(geo: LrcGeometry, missing: list[int],
                available: "list[int] | None" = None) -> RepairPlan:
    """The reconstruct planner.

    Single failure inside one local group (data or the group's local
    parity): repair from the group's surviving members — k/l reads.
    Anything else: global solve from k available rows whose submatrix
    of the generator is invertible."""
    missing = sorted(set(missing))
    if available is None:
        available = [s for s in range(geo.n) if s not in missing]
    else:
        available = [s for s in available if s not in missing]

    if len(missing) == 1:
        s = missing[0]
        g = None
        if s < geo.k:
            g = geo.group_of(s)
        elif s < geo.k + geo.l:
            g = s - geo.k
        if g is not None:
            group = geo.group_members(g) + [geo.local_parity_index(g)]
            reads = [x for x in group if x != s]
            if all(x in available for x in reads):
                # XOR of the group's survivors reproduces the missing one
                m = np.ones((1, len(reads)), dtype=np.uint8)
                return RepairPlan("local", reads, m, missing)

    # global: greedily pick k linearly-independent available rows via GF
    # Gaussian elimination — finds a solvable subset whenever ONE exists
    # (rank(available rows) == k), unlike any fixed-window scan
    G = generator_matrix(geo)
    rows = _independent_rows(G, available, geo.k)
    if rows is None:
        raise ValueError(f"unrecoverable: missing={missing}, "
                         f"available={available}")
    inv = gf256.mat_inv(G[rows])
    # data = inv @ read_shards; missing shard s = G[s] @ data
    want = gf256.matmul(G[missing], inv)
    return RepairPlan("global", rows, want, missing)


def _independent_rows(G: np.ndarray, candidates: list[int],
                      k: int) -> "list[int] | None":
    """First k rows of G[candidates] that are linearly independent over
    GF(2^8), by incremental elimination; None if rank < k."""
    basis: list[np.ndarray] = []
    pivots: list[int] = []
    chosen: list[int] = []
    for r in candidates:
        v = G[r].copy()
        for b, p in zip(basis, pivots):
            if v[p]:
                v = v ^ gf256.mul(gf256.div(v[p], b[p]), b)
        nz = np.nonzero(v)[0]
        if len(nz) == 0:
            continue  # dependent on chosen rows
        basis.append(v)
        pivots.append(int(nz[0]))
        chosen.append(r)
        if len(chosen) == k:
            return chosen
    return None


def repair(geo: LrcGeometry, plan: RepairPlan,
           shard_data: dict[int, np.ndarray]) -> dict[int, np.ndarray]:
    """Execute a plan: shard_data maps shard id -> [B] bytes for every
    shard in plan.read_shards.  Returns {missing shard id: bytes}."""
    stack = np.stack([shard_data[s] for s in plan.read_shards])
    out = gf256.matmul(plan.matrix, stack)
    return {s: out[i] for i, s in enumerate(plan.missing)}


def encode_shards(geo: LrcGeometry, data: np.ndarray) -> np.ndarray:
    """[k, B] -> all [n, B] shards (data + locals + globals)."""
    return np.concatenate([data, encode(geo, data)], axis=0)
