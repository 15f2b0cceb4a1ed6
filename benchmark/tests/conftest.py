"""benchmark/tests run on the CPU, at sizes a test can hold: the
harness's CPU path is reached through run_cell(platform="cpu")."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("WEED_LOCKDEP", "0")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
