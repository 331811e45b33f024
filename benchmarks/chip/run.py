"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload nq768-batch --seed 7 --seconds 30 --trace 0

Builds the cell's corpus on the device from the seed, hands it to the
program's serving engine, warms every shape the window uses, measures for
``--seconds``, checks the window's answers against the plain reference and
prints one JSON line last: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, last, ``checks``. Exits non-zero, with no
result, where jax finds no accelerator or fewer chips than the cell asks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import spec

    cell = spec.resolve(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} accelerator chip(s); jax "
              f"finds {len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    import repro  # noqa: F401  (the program must be there before set-up starts)

    from chipbench import runner

    runner.enable_cache()
    result, lines = runner.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
