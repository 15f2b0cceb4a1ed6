"""The LRC cell's own control.  plant.control builds every RS matrix by
the Cauchy construction, and LRC's global rows are no RS matrix, so
that control leaves an LRC seal as it was.  Here the program's LRC
global rows are the points 1..12 and their squares, the coefficients
ops/lrc.py used before Azure's Maximally Recoverable construction: the
seal then differs from reference/lrc.py's, and `correct` must come out
false."""

import numpy as np

from benchmark import core
from benchmark.tests import plant

CELL = "lrc12_2_2.repair"


def test_other_global_coefficients_turn_correct_false(monkeypatch):
    from seaweedfs_tpu.ops import gf256, lrc
    built = lrc.generator_matrix

    def former(geo):
        G = built(geo).copy()
        pts = np.arange(1, geo.k + 1, dtype=np.uint8)
        for j in range(geo.r):
            G[geo.k + geo.l + j] = gf256.gf_pow(pts, j + 1)
        return G
    monkeypatch.setattr(lrc, "generator_matrix", former)
    r = core.run_cell(CELL, 2**31 + 29, 1.0, False, "cpu",
                      expect=plant.cpu_expect(CELL),
                      sizes={"volume_size_mb": 24})
    assert not r["correct"], r["compared"]
    assert r["compared"]["sealed_shards_differing"]["value"] == 2
