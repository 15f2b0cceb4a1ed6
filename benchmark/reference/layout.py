"""SeaweedFS's EC stripe layout (weed/storage/erasure_coding/
ec_encoder.go:17-23, ec_locate.go): a .dat is cut row-major into rows of
k blocks, 1 GiB blocks while a whole large row fits, then 1 MiB blocks,
the last small row zero-padded; block i of a row goes to shard i."""

from __future__ import annotations

import numpy as np


def data_shards(dat: np.ndarray, k: int, large: int, small: int
                ) -> np.ndarray:
    """[k, shard_size] uint8: the data shard files of `dat`."""
    size = len(dat)
    n_large = size // (k * large)
    rest = size - n_large * k * large
    n_small = -(-rest // (k * small))
    out = np.zeros((k, n_large * large + n_small * small), dtype=np.uint8)
    big = dat[:n_large * k * large].reshape(n_large, k, large)
    for r in range(n_large):
        out[:, r * large:(r + 1) * large] = big[r]
    tail = np.zeros(n_small * k * small, dtype=np.uint8)
    tail[:rest] = dat[n_large * k * large:]
    out[:, n_large * large:] = tail.reshape(n_small, k, small) \
        .transpose(1, 0, 2).reshape(k, n_small * small)
    return out
