#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces.

Run on one TPU from the root of a checkout:
``python3 bench/tests/record_trace.py <out.xplane.pb>``. Inside the
harness's window annotation it evaluates and then garbles one small
netlist with the Pallas executor, with host sleeps between them, and
prints the host clock at which the window was entered.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import trace as T  # noqa: E402
from repro.config import PrivacyConfig  # noqa: E402
from repro.core import garble as G  # noqa: E402
from repro.core.protocol import PiTProtocol  # noqa: E402

INSTANCES = 64
SLEEP_S = 0.05


def main() -> int:
    out = Path(sys.argv[1])
    p = PiTProtocol(PrivacyConfig(he_t_bits=40, frac_bits=7), seed=0)
    net = p.trunc_net(2 * p.frac)
    key = jax.random.PRNGKey(0)

    def garble():
        gc = G.garble(net, key, INSTANCES, impl="pallas")
        jax.block_until_ready(gc.tables)
        return gc

    def evaluate(gc):
        cw, c_lab = G.const_wires_labels(gc)
        ids = np.concatenate([net.garbler_inputs, net.evaluator_inputs,
                              cw]).astype(np.int64)
        zeros = np.zeros((INSTANCES, len(net.garbler_inputs)), np.int64)
        ez = np.zeros((INSTANCES, len(net.evaluator_inputs)), np.int64)
        labels = jnp.concatenate([
            G.encode_inputs(gc, net.garbler_inputs, zeros),
            G.encode_inputs(gc, net.evaluator_inputs, ez), c_lab], 1)
        return jax.block_until_ready(
            G.evaluate(net, gc.tables, (ids, labels), impl="pallas"))

    gc = garble()
    evaluate(gc)  # compile both walks outside the trace
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        with T.recording(tdir):
            anchor = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(T.WINDOW):
                time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("bench.request"):
                    evaluate(gc)
                time.sleep(SLEEP_S)
                with jax.profiler.TraceAnnotation("bench.bundle"):
                    garble()
        shutil.copy(T.find_xplane(tdir), out)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(f"anchor_ns={anchor} ands={net.and_count} "
          f"bytes={out.stat().st_size}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
