"""Azure's Local Reconstruction Code LRC(k, l, r) (Huang et al., Erasure
Coding in Windows Azure Storage, USENIX ATC 2012), from the paper's
description: k data fragments in l local groups of k/l; each group's
local parity the XOR of its members; r global parities over all k.
Global parity j (j = 1..r) is the sum of point_i^j * d_i, where the data
fragments of group g take the points {1, ..., k/l} shifted left by 4g:
group 0's in the low bits of GF(2^8), group 1's in the high ones, so no
sum of one group's points meets a sum of the other's (the paper's
Maximally Recoverable choice).  Fragment order: data 0..k-1, the local
parities k..k+l-1, the global parities k+l..k+l+r-1."""

from __future__ import annotations

import numpy as np

from . import gf256


def points(k: int, l: int) -> list[int]:
    size = k // l
    return [(i + 1) << (4 * g) for g in range(l) for i in range(size)]


def parity_rows(k: int, l: int, r: int) -> list[list[int]]:
    """The l local rows, then the r global rows, over the k data."""
    size = k // l
    local = [[int(c // size == g) for c in range(k)] for g in range(l)]
    pts = points(k, l)
    glob = [[gf256.power(p, j) for p in pts] for j in range(1, r + 1)]
    return local + glob


def encode(data: np.ndarray, l: int, r: int) -> np.ndarray:
    """data [k, n] uint8 -> the l + r parity fragments [l + r, n]."""
    k = data.shape[0]
    return gf256.combine(parity_rows(k, l, r), [data[i] for i in range(k)])


def local_group(shard: int, k: int, l: int) -> list[int]:
    """The fragments a single loss of data or local parity `shard` is
    repaired from: the other members of its group, and the group's
    local parity."""
    size = k // l
    g = shard // size if shard < k else shard - k
    return [s for s in list(range(g * size, (g + 1) * size)) + [k + g]
            if s != shard]


def local_repair(shards: dict[int, np.ndarray], shard: int, k: int,
                 l: int) -> np.ndarray:
    """The lost fragment `shard` as the XOR of its group's others."""
    return np.bitwise_xor.reduce(
        np.stack([shards[s] for s in local_group(shard, k, l)]), axis=0)
