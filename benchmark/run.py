"""Entry point: python benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>.  See benchmark/core.py."""

import os
import sys

# the checkout's root in place of this directory, whose module names
# (trace, core) would shadow the standard library's
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import core  # noqa: E402

if __name__ == "__main__":
    sys.exit(core.main())
