#!/usr/bin/env python3
"""Rehearse a cell here, without the chip (on-chip-measurement guide §2.1).

  JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <name> \
      [--seed 7] [--seconds 4] [--trace 0]

Runs the same `run_cell` as run.py at the configuration's tiny
`rehearsal` sizes (256-row batches, 2^21 buckets, `kernel=pallas`, i.e.
the Pallas kernels interpreted), skipping only the look for a chip. It
finds wrong paths, file names and control flow before chip time is spent:
a cell a later PR adds as files can be checked this way. The line it
prints names the platform it ran on (`cpu`); its numbers are not
measurements of anything a user runs, and `--trace 1` fails here because
a CPU trace holds no device plane.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    out = run.run_cell(bench, args.workload, args.seed, args.seconds,
                       bool(args.trace), rehearsal=True)
    print(json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
