"""The repair read-set planner, one for the shell and the volume server.

`ec.rebuild` (shell/command_ec.py) asks it which shards a repair reads,
picks as rebuilder the server that already holds most of them, and
copies it only the rest; the rebuilder's VolumeEcShardsRebuild
(encoder.rebuild_ec_files) asks it again over what it then holds and
reads exactly that set.  Per code family:

  rs    any k survivors, those on the rebuilder (`prefer`) first
  clay  one loss: the d = n-1 helpers (their beta planes are read,
        and the shell copies the rebuilder only those planes of each
        remote helper, a quarter of the shard for clay(10,4));
        more losses: k survivors, as RS
  lrc   ops/lrc.plan_repair: one lost data or local-parity shard reads
        the other members of its local group; anything else reads k
        independent rows
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ...ops import clay_matrix, lrc
from .layout import EcGeometry


@dataclass(frozen=True)
class RepairPlan:
    """kind: rs-full | clay-plane | clay-decode | local | global.
    matrix (LRC) [len(missing), len(read_shards)] over GF(2^8) gives the
    missing shards from the read ones, in read_shards' order; None for
    RS, whose codec decodes from its own generator, and for clay, whose
    repair is not one matrix over whole shards."""
    kind: str
    read_shards: tuple[int, ...]
    missing: tuple[int, ...]
    matrix: "np.ndarray | None"


def lrc_geometry(geo: EcGeometry) -> lrc.LrcGeometry:
    if not geo.lrc_locals or geo.data_shards % geo.lrc_locals:
        raise ValueError(
            f"lrc needs lrc_locals dividing k: k={geo.data_shards} "
            f"l={geo.lrc_locals}")
    return lrc.LrcGeometry(k=geo.data_shards, l=geo.lrc_locals,
                           r=geo.parity_shards - geo.lrc_locals)


def repair_plan(geo: EcGeometry, missing, available,
                prefer=()) -> RepairPlan:
    """The shards that regenerate `missing` from `available`, taking the
    shards in `prefer` (those already on the rebuilder) first wherever
    the code leaves a choice.  Raises ValueError when `available` cannot
    regenerate `missing`."""
    missing = sorted(set(missing))
    prefer = set(prefer)
    left = [s for s in available if s not in missing]
    order = sorted(s for s in left if s in prefer) \
        + sorted(s for s in left if s not in prefer)
    k, m = geo.data_shards, geo.parity_shards
    if geo.code_kind == "lrc":
        p = lrc.plan_repair(lrc_geometry(geo), missing, available=order)
        return RepairPlan(p.kind, tuple(p.read_shards), tuple(missing),
                          p.matrix)
    if len(order) < k:
        raise ValueError(f"need >= {k} shards to rebuild {missing}, "
                         f"have {len(order)}")
    if geo.code_kind == "clay":
        if len(missing) == 1:
            helpers = sorted(clay_matrix.code(k, m).repair_plan(missing[0]))
            if set(helpers) <= set(order):
                return RepairPlan("clay-plane", tuple(helpers),
                                  tuple(missing), None)
        return RepairPlan("clay-decode", tuple(sorted(order[:k])),
                          tuple(missing), None)
    return RepairPlan("rs-full", tuple(sorted(order[:k])), tuple(missing),
                      None)
