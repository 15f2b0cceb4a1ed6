"""Clay code (Vajha et al., "Clay Codes: Moulding MDS Codes to Yield Vector
Codes", FAST 2018), the construction Ceph ships as its `clay` plugin, with
the parameters this deployment runs: q = m, t = ceil((k+m)/q), n0 = q*t
nodes on a q x t grid (node i at x = i % q, y = i // q), sub-packetization
alpha = q**t, beta = alpha / q, d = n0 - 1 helpers.

- Internal nodes 0..k-1 are data, k..n0-m-1 are shortened (always zero,
  never stored), n0-m..n0-1 are parity (external ids k..k+m-1).
- Layer z in [0, alpha) has digits z_y = (z // q**y) % q.
- Uncoupled symbols U of each layer form a codeword of the systematic
  (n0, n0-m) RS code of rs.generator (the layer MDS code).
- Stored symbols C couple in pairs: for node (x, y) in layer z with
  z_y != x, its companion is node (z_y, y) in layer z with digit y set to
  x, and U = C + g * C_companion with g = 2; where z_y == x, U = C.

A shard file stores each 1 MiB window as [alpha, window/alpha], layer
major.  encode and repair take and return [nodes, alpha, B] arrays whose
B axis runs over all windows."""

from __future__ import annotations

import numpy as np

from . import gf256, rs

GAMMA = 2


class Clay:
    def __init__(self, k: int, m: int):
        self.k, self.m = k, m
        self.q = m
        self.t = -(-(k + m) // m)
        self.n0 = self.q * self.t
        self.alpha = self.q ** self.t
        self.beta = self.alpha // self.q
        self.k0 = self.n0 - m
        self.gen = [list(r) for r in rs.generator(self.k0, m)]

    def internal(self, ext: int) -> int:
        return ext if ext < self.k else self.n0 - self.m + ext - self.k

    def _solve(self, known: list[int], unknown: list[int]
               ) -> list[list[int]]:
        """Coefficients of U[unknown] over U[known] (len k0) in a layer."""
        inv = gf256.mat_inv([self.gen[i] for i in known])
        return gf256.mat_mul([self.gen[i] for i in unknown], inv)

    def _uncouple(self, cells: dict, node: int, layers: np.ndarray
                  ) -> np.ndarray:
        """U[node] at `layers` ([len(layers), B]) from stored symbols."""
        x, y = node % self.q, node // self.q
        out = cells[node][layers].copy()
        w = (layers // self.q ** y) % self.q
        for other in range(self.q):
            pick = w == other
            if other == x or not pick.any():
                continue
            zc = layers[pick] + (x - other) * self.q ** y
            mate = cells[y * self.q + other][zc]
            out[pick] ^= gf256.combine([[GAMMA]], [mate.reshape(-1)])[0] \
                .reshape(mate.shape)
        return out

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k, alpha, B] -> parity [m, alpha, B]."""
        k, alpha, b = data.shape
        zero = np.zeros((alpha, b), dtype=np.uint8)
        cells = {i: (data[i] if i < k else zero) for i in range(self.k0)}
        every = np.arange(alpha)
        u_known = [self._uncouple(cells, i, every).reshape(-1)
                   for i in range(self.k0)]
        parity_nodes = list(range(self.k0, self.n0))
        coefs = self._solve(list(range(self.k0)), parity_nodes)
        u_par = gf256.combine(coefs, u_known).reshape(self.m, alpha, b)
        # every parity node sits in the last column: a coupled pair
        # (U1, U2) = (C1 + g C2, C2 + g C1) gives C1 = (U1 + g U2)/(1+g^2)
        det = gf256.inv(1 ^ gf256.mul(GAMMA, GAMMA))
        y = self.t - 1
        out = u_par.copy()
        w = (every // self.q ** y) % self.q
        for j, node in enumerate(parity_nodes):
            x = node % self.q
            for other in range(self.q):
                if other == x:
                    continue
                zs = every[w == other]
                zc = zs + (x - other) * self.q ** y
                mate = u_par[other][zc]
                out[j, zs] = gf256.combine(
                    [[det, gf256.mul(det, GAMMA)]],
                    [u_par[j, zs].reshape(-1), mate.reshape(-1)])[0] \
                    .reshape(mate.shape)
        return out

    def repair(self, lost_ext: int, helpers: dict[int, np.ndarray]
               ) -> np.ndarray:
        """helpers: external id -> [beta, B], that helper's layers of the
        repair plane z_{y0} = x0 in ascending z, for every surviving
        node.  -> the lost node's [alpha, B]."""
        lost = self.internal(lost_ext)
        x0, y0 = lost % self.q, lost // self.q
        every = np.arange(self.alpha)
        plane = every[(every // self.q ** y0) % self.q == x0]
        b = next(iter(helpers.values())).shape[-1]
        zero = np.zeros((self.alpha, b), dtype=np.uint8)
        cells = {node: zero for node in range(self.n0)}
        for ext, sym in helpers.items():
            full = np.zeros((self.alpha, b), dtype=np.uint8)
            full[plane] = sym
            cells[self.internal(ext)] = full
        column = [y0 * self.q + x for x in range(self.q)]
        known = [i for i in range(self.n0) if i not in column]
        coefs = self._solve(known, column)
        u_col = gf256.combine(
            coefs, [self._uncouple(cells, i, plane).reshape(-1)
                    for i in known]).reshape(self.q, len(plane), b)
        out = np.zeros((self.alpha, b), dtype=np.uint8)
        ginv = gf256.inv(GAMMA)
        for j, node in enumerate(column):
            if node == lost:
                out[plane] = u_col[j]
                continue
            # U[h, z] = C[h, z] + g C[lost, z'], with z' = z but digit
            # y0 set to h's x: the lost node's out-of-plane symbols
            zp = plane + (node % self.q - x0) * self.q ** y0
            diff = u_col[j] ^ cells[node][plane]
            out[zp] = gf256.combine([[ginv]], [diff.reshape(-1)])[0] \
                .reshape(diff.shape)
        return out


def to_layers(shard: np.ndarray, alpha: int, small: int) -> np.ndarray:
    """Shard bytes [n_win * small] -> [alpha, n_win * small / alpha]."""
    n_win = len(shard) // small
    return np.ascontiguousarray(
        shard.reshape(n_win, alpha, small // alpha).transpose(1, 0, 2)
    ).reshape(alpha, -1)


def from_layers(sym: np.ndarray, small: int) -> np.ndarray:
    alpha = sym.shape[0]
    win_a = small // alpha
    n_win = sym.shape[1] // win_a
    return np.ascontiguousarray(
        sym.reshape(alpha, n_win, win_a).transpose(1, 0, 2)).reshape(-1)
