#!/usr/bin/env python3
"""Read the lower-precision control of a cell on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> SEED [SEED ...]

The control is the program itself, run with one fractional bit fewer
than the configuration states (``privacy.frac_bits`` - 1), the step that
shrinks every garbled circuit. Each seed is one whole run of the cell as
``run.py`` makes it, in this one process, compared with the reference at
the configuration's own precision. It prints one JSON line per seed with
the compared numbers; the benchmark's own runs never run it. A limit is
set between the largest reading of sound runs and the smallest of these.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import prepare, tpu_device

    prepare()
    from bench import harness

    device = tpu_device()
    if device["platform"] != "tpu":
        print(f"control: no TPU (JAX sees {device})", file=sys.stderr)
        return 2
    for seed in args.seeds:
        cell = harness.load_cell(ROOT, args.workload)
        frac = cell.config["privacy"]["frac_bits"] - 1
        res = harness.run_cell(ROOT, cell, seed, args.seconds, False,
                               time.perf_counter(), dict(device),
                               frac_bits=frac)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program_frac_bits": frac,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
