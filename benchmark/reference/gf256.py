"""GF(2^8) over x^8 + x^4 + x^3 + x^2 + 1 (0x11D) with generator 2: the
field of Backblaze's JavaReedSolomon and klauspost/reedsolomon, which
SeaweedFS's ec_encoder.go drives.  Copied from the field's definition,
not from the program."""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor

import numpy as np

POLY = 0x11D


def _tables() -> tuple[list[int], list[int]]:
    exp, log = [0] * 510, [0] * 256
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return EXP[LOG[a] + LOG[b]]


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return EXP[255 - LOG[a]]


def power(a: int, n: int) -> int:
    """a**n, with 0**0 == 1 (klauspost galExp)."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return EXP[(LOG[a] * n) % 255]


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = []
    for row in a:
        acc = [0] * len(b[0])
        for coef, brow in zip(row, b):
            if coef:
                acc = [x ^ mul(coef, y) for x, y in zip(acc, brow)]
        out.append(acc)
    return out


def mat_inv(a: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = len(a)
    aug = [list(row) + [int(i == j) for j in range(n)]
           for i, row in enumerate(a)]
    for col in range(n):
        pivot = next((r for r in range(col, n) if aug[r][col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        aug[col], aug[pivot] = aug[pivot], aug[col]
        scale = inv(aug[col][col])
        aug[col] = [mul(scale, x) for x in aug[col]]
        for r in range(n):
            if r != col and aug[r][col]:
                f = aug[r][col]
                aug[r] = [x ^ mul(f, y) for x, y in zip(aug[r], aug[col])]
    return [row[n:] for row in aug]


@functools.lru_cache(maxsize=256)
def _pair_table(c: int) -> np.ndarray:
    """c * x applied to both bytes of a 16-bit word at once."""
    byte = np.array([mul(c, x) for x in range(256)], dtype=np.uint16)
    v = np.arange(65536, dtype=np.uint32)
    return (byte[v & 0xFF] | (byte[v >> 8] << 8)).astype(np.uint16)


def _combine_block(coefs: list[list[int]], rows: list[np.ndarray],
                   out: np.ndarray) -> None:
    """out[p] = sum_i coefs[p][i] * rows[i] on one column block (even
    length, so the 16-bit view is exact)."""
    words = [r.view(np.uint16) for r in rows]
    tmp = np.empty_like(words[0])
    for p, row_coefs in enumerate(coefs):
        acc = out[p].view(np.uint16)
        acc[:] = 0
        for c, w in zip(row_coefs, words):
            if c == 0:
                continue
            if c == 1:
                acc ^= w
                continue
            np.take(_pair_table(c), w, out=tmp)
            acc ^= tmp


def combine(coefs: list[list[int]], rows: list[np.ndarray],
            block: int = 4 << 20, threads: int = 8) -> np.ndarray:
    """[len(coefs), n] uint8: each output row the GF(2^8) linear
    combination of `rows` (equal-length uint8 vectors) by its coefs."""
    n = len(rows[0])
    if n & 1:
        rows = [np.concatenate([r, np.zeros(1, np.uint8)]) for r in rows]
    padded = n + (n & 1)
    out = np.zeros((len(coefs), padded), dtype=np.uint8)

    def work(lo: int) -> None:
        hi = min(padded, lo + block)
        _combine_block(coefs, [np.ascontiguousarray(r[lo:hi]) for r in rows],
                       out[:, lo:hi])

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(work, range(0, padded, block)))
    return out[:, :n]
