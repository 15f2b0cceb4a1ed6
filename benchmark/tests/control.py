"""Runs of one cell in one process, on several seeds, with the program
as it is, with the control, or with a planted fault:

    python benchmark/tests/control.py --cell rs10_4.seal --seeds 1,2,3 \\
        --plant control --seconds 5

prints one JSON line per seed: its seed, `correct` and the numbers
compared.  On the chip it gives the control's readings at the cell's own
size; --cpu (with --size-mb) runs it here, as test_faults.py does."""

from __future__ import annotations

import argparse
import json
import os
import sys
from contextlib import nullcontext

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import core  # noqa: E402
from benchmark.tests import plant  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plant", default="none")
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--size-mb", type=int, default=0)
    args = ap.parse_args()
    platform = "cpu" if args.cpu else "tpu"
    expect = plant.cpu_expect(args.cell) if args.cpu else None
    sizes = {"volume_size_mb": args.size_mb} if args.size_mb else None
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.plant == "control":
            ctx = plant.control()
        elif args.plant.startswith("fault:"):
            import jax
            ctx = plant.fault(args.cell, args.plant[len("fault:"):],
                              len(jax.devices()))
        else:
            ctx = nullcontext()
        with ctx:
            r = core.run_cell(args.cell, seed, args.seconds, False,
                              platform, expect=expect, sizes=sizes)
        print(json.dumps({"seed": seed, "plant": args.plant,
                          "correct": r["correct"],
                          "metrics": r["metrics"],
                          "device": r["device"],
                          "compared": r["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
