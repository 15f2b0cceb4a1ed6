"""Systematic Reed-Solomon RS(k, m) as klauspost/reedsolomon's default
New(k, m) builds it (the Backblaze construction): the (k+m) x k
Vandermonde matrix vm[r][c] = r**c, times the inverse of its top k x k
square, so the top k rows are the identity."""

from __future__ import annotations

import functools

import numpy as np

from . import gf256


@functools.lru_cache(maxsize=16)
def generator(k: int, m: int) -> tuple[tuple[int, ...], ...]:
    vm = [[gf256.power(r, c) for c in range(k)] for r in range(k + m)]
    gen = gf256.mat_mul(vm, gf256.mat_inv(vm[:k]))
    assert all(gen[i][j] == int(i == j) for i in range(k) for j in range(k))
    return tuple(tuple(row) for row in gen)


def encode(data: np.ndarray, m: int) -> np.ndarray:
    """data [k, n] uint8 -> parity [m, n]."""
    k = data.shape[0]
    rows = [list(r) for r in generator(k, m)[k:]]
    return gf256.combine(rows, [data[i] for i in range(k)])


def decode_rows(k: int, m: int, present: list[int],
                targets: list[int]) -> list[list[int]]:
    """Coefficients recovering shards `targets` from shards present[:k]."""
    gen = [list(r) for r in generator(k, m)]
    inv = gf256.mat_inv([gen[i] for i in present[:k]])
    return gf256.mat_mul([gen[t] for t in targets], inv)


def encode_rows(data: np.ndarray, rows: list[list[int]]) -> np.ndarray:
    """Only the given generator rows (e.g. the parity shards kept)."""
    return gf256.combine(rows, [data[i] for i in range(data.shape[0])])
