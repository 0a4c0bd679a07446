"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload vit256.surveil --seed 7 \
        --seconds 10 --trace 0

Loads the cell named in ``BENCHMARK.json``, warms up, serves its traffic
for ``--seconds``, checks the served logits against the plain reference,
and prints one JSON object as the last line of standard output. Exits
non-zero, printing no result, without a TPU. ``--streams`` overrides the
traffic's camera count (for finding the knee); ``--control 1``
additionally reads the lower-precision control on the same frames.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--streams", type=int, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.report(harness.run(args, ROOT, t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
