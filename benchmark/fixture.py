"""A volume of blobs: sizes, keys, cookies, contents.

The blob sizes (log-uniform over the configuration's range), their order
in the volume and their popularity ranks come from a fixed stream, the
same for every seed; --seed draws the contents, the cookies and, in the
read traffic, the order of the requests.  So seeds do the same work in
another order: a seed that moved a hot blob onto a lost shard would
change how many reads need a reconstruct, and with it the rate (PR 22's
first chip runs: 71 and 88 reads/s on two seeds)."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .reference import needle

_SIZES_SEED = 20141006     # f4's OSDI'14 date: a fixed stream, not --seed
APPEND_NS = 1_700_000_000_000_000_000


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), *stream])


@dataclass
class Volume:
    vid: int
    collection: str
    keys: np.ndarray
    cookies: np.ndarray
    sizes: np.ndarray
    content: bytes
    hot: "np.ndarray | None" = None     # blob index by popularity rank
    offsets: "np.ndarray | None" = None
    _dat: "np.ndarray | None" = None

    @property
    def starts(self) -> np.ndarray:
        return np.concatenate([[0], np.cumsum(self.sizes)[:-1]]) \
            .astype(np.int64)

    def blob(self, i: int) -> bytes:
        s = int(self.starts[i])
        return self.content[s:s + int(self.sizes[i])]

    def fid(self, i: int) -> str:
        return needle.fid(self.vid, int(self.keys[i]), int(self.cookies[i]))

    def dat(self) -> np.ndarray:
        """The .dat bytes (built once)."""
        if self._dat is None:
            self._dat, self.offsets = needle.build_volume(
                self.keys, self.cookies, self.sizes, self.content, APPEND_NS)
        return self._dat

    def index(self) -> bytes:
        self.dat()
        return needle.index_bytes(self.keys, self.offsets, self.sizes + 5)

    def ecx(self) -> bytes:
        self.dat()
        return needle.ecx_bytes(self.keys, self.offsets, self.sizes + 5)

    def write(self, base: str) -> None:
        """<base>.dat and <base>.idx, as a volume server leaves them."""
        self.dat().tofile(base + ".dat")
        with open(base + ".idx", "wb") as f:
            f.write(self.index())


def make_volume(config: dict, seed: int, vid: int = 1) -> Volume:
    """Blobs filling config["volume_size_mb"] MiB of .dat."""
    lo, hi = config["blob_size_min"], config["blob_size_max"]
    target = config["volume_size_mb"] << 20
    fixed = np.random.default_rng(_SIZES_SEED)
    sizes: list[int] = []
    total = len(needle.SUPER_BLOCK)
    while True:
        s = int(np.exp(fixed.uniform(np.log(lo), np.log(hi))))
        if total + needle.record_length(s) > target:
            break
        sizes.append(s)
        total += needle.record_length(s)
    order = fixed.permutation(len(sizes))
    sizes_arr = np.asarray(sizes, dtype=np.int64)[order]
    n = len(sizes_arr)
    raw = np.random.SFC64(np.random.SeedSequence([seed % (1 << 63), 2])) \
        .random_raw(-(-int(sizes_arr.sum()) // 8))
    content = raw.view(np.uint8)[:int(sizes_arr.sum())].tobytes()
    cookies = rng(seed, 3).integers(0, 1 << 32, n, dtype=np.uint64)
    keys = np.arange(1, n + 1, dtype=np.uint64)
    return Volume(vid, config["collection"], keys, cookies, sizes_arr,
                  content, hot=fixed.permutation(n))
