"""Puts the benchmark's harness and the program on the path of its tests.

They run on the CPU only and make no chip or topology call."""

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
