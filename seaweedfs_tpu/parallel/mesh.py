"""Device-mesh helpers for the distributed EC engine.

The reference scales EC work by fanning goroutines out across volume servers
over gRPC (weed/shell/command_ec_encode.go:190 parallelCopyEcShardsFromSource;
weed/storage/store_ec.go:338 scatter-gather shard reads).  The TPU-native
equivalent keeps that gRPC control plane on the host but moves the *math* onto
an ICI-connected chip mesh: volumes are data-parallel across chips, and a
volume's shard blocks can additionally be sharded along the byte axis
(sequence-parallel analogue) with mod-2 psum collectives doing cross-chip
XOR-reduction.

Axis names:
  "v"  — volume data-parallel axis (independent volumes, no collectives)
  "b"  — byte/block axis within a volume (encode is columnwise-independent,
         so sharding B needs no collectives either; reconstruct gathers are
         rides on ICI)
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_volume: int | None = None, n_byte: int = 1,
              devices=None) -> Mesh:
    """(v, b) mesh over all (or given) devices; defaults to pure volume-DP."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if n_volume is None:
        n_volume = devices.size // n_byte
    assert n_volume * n_byte == devices.size, (n_volume, n_byte, devices.size)
    return Mesh(devices.reshape(n_volume, n_byte), axis_names=("v", "b"))


def volume_sharding(mesh: Mesh) -> NamedSharding:
    """[V, k, B] with volumes split over 'v' and bytes over 'b'."""
    return NamedSharding(mesh, P("v", None, "b"))
