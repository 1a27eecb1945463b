#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout. With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
and the device's busy time from a profiler trace of the window. Progress,
the split of set-up time and the compared numbers go to standard error;
the last line of standard output is the result, one JSON object. With no
TPU, or fewer chips than the cell asks for, it prints no result and exits
with code 2. JAX's persistent compilation cache is kept in ``.jax_cache/``
at the root of the checkout, so only the first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def prepare() -> None:
    """Put the compile cache at its fixed place and the program on the
    path. Call before JAX is imported."""
    # the cache's key holds its path, so it stays at one fixed place;
    # small programs are kept too, so a warm run compiles nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def tpu_device() -> dict:
    """The devices JAX sees, as the result's ``device`` reports them."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    prepare()
    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    device = tpu_device()
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"bench: this cell needs {cell.chips} TPU chip(s); JAX sees "
              f"{device}. It does not fall back to the CPU.",
              file=sys.stderr)
        return 2
    harness.log(f"device: {device}")
    result = harness.run_cell(ROOT, cell, args.seed, args.seconds,
                              bool(args.trace), T_START, device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
