#!/usr/bin/env python3
"""The on-chip benchmark of the protected training job.

    python3 benchmarks/chip/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the cell's chips.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its limit.
Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))     # the system under test
sys.path.insert(0, str(HERE.parent))                 # this package: chip


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chip.harness import main as run_main
    return run_main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
