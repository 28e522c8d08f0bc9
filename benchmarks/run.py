#!/usr/bin/env python3
"""The benchmark's command: one run of one cell on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

Every line of standard output is one JSON object; the last is the result
(`correct`, `attempted`, `failed`, `metrics`, `device`, and with
`--trace 1` or `2` `breakdown`). `--trace 2` is a `--trace 0` run that,
once its window has closed, traces a few seconds more and prints the
end-to-end and the per-layer metrics in one line. It refuses to run
without a TPU, with fewer devices than the cell's chips, or with
FLEXFLOW_TPU_PALLAS set: there is no CPU fallback. One process; nothing
outlives it.
"""

import time

T_START = time.perf_counter()   # set-up counts from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = ap.parse_args()
    if "FLEXFLOW_TPU_PALLAS" in os.environ:
        raise SystemExit("benchmark: unset FLEXFLOW_TPU_PALLAS; the kernels "
                         "must take their TPU path")
    if not os.path.isdir(os.path.join(ROOT, "flexflow_tpu")):
        raise SystemExit("benchmark: the program (flexflow_tpu/) is not in "
                         "this checkout")
    from benchmarks.harness import run_cell
    run_cell(args.workload, args.seed, args.seconds, args.trace,
             t_start=T_START)


if __name__ == "__main__":
    main()
