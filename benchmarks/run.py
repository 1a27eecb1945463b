# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator.

  fig5b  — bench_mult_ands      (64-bit multiplier AND counts)
  fig9a  — bench_circuit_ands   (per-function AND reduction)
  fig8a  — bench_accuracy       (private-vs-float parity)
  fig8b  — bench_protocol       (offline/online latency stack)
  fig10  — bench_sched          (scheduling/speculation/accelerator)
  fig11b — bench_energy         (system energy HAAC vs APINT)
  kernels / roofline            (unit costs, dry-run roofline table)
  gc_eval — bench_gc_eval       (device GC executor vs numpy loop; smoke
                                 here, full sweep writes BENCH_gc_eval.json)
  net    — bench_net            (two-party runtime: transports, ledger
                                 parity, pipelined refill; full run writes
                                 BENCH_net.json)

``--check`` runs ONLY the regression gates: the gc_eval gate re-measures
a subset of the committed ``BENCH_gc_eval.json`` trajectory and fails on
a >20% speedup regression; the net gate re-derives the smoke-config wire
oracle and fails on a >20% byte — or any round-count — regression
against the committed ``BENCH_net.json``, and holds the tracing-off
cost of the ``repro.obs`` instrumentation below 1% of the smoke point
(CI runs all of it right after the bench smoke).

``--trace [PATH]`` records the whole suite with ``repro.obs`` and
exports a Chrome trace_event JSON (default ``bench_trace.json``).
"""

from __future__ import annotations

import sys
import traceback
from pathlib import Path

# script-style invocation (`python benchmarks/run.py`) puts benchmarks/
# itself on sys.path, not the repo root that the `benchmarks.*`
# namespace imports need — add it (harmless under `-m benchmarks.run`)
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def check() -> None:
    import jax

    jax.config.update("jax_enable_x64", True)
    from benchmarks import bench_gc_eval, bench_net

    bench_gc_eval.check()
    bench_net.check()


def main(trace: str | None = None) -> None:
    import jax

    jax.config.update("jax_enable_x64", True)  # privacy plane (HE uint64)
    tr = None
    if trace:
        from repro import obs

        tr = obs.enable()
    print("name,us_per_call,derived")
    from benchmarks import (
        bench_mult_ands,
        bench_circuit_ands,
        bench_kernels,
        bench_accuracy,
        bench_protocol,
        bench_sched,
        bench_energy,
        bench_roofline,
        bench_gc_eval,
        bench_net,
    )

    suites = [
        ("fig5b", bench_mult_ands),
        ("fig9a", bench_circuit_ands),
        ("kernels", bench_kernels),
        ("fig8a", bench_accuracy),
        ("fig8b", bench_protocol),
        ("fig10", bench_sched),
        ("fig11b", bench_energy),
        ("roofline", bench_roofline),
        ("gc_eval", bench_gc_eval),
        ("net", bench_net),
    ]
    failed = []
    for name, mod in suites:
        print(f"# --- {name} ({mod.__name__}) ---", flush=True)
        try:
            mod.main()
        except Exception as e:  # keep the suite running
            traceback.print_exc()
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
    if tr is not None:
        tr.export(trace)
        print(f"# wrote trace: {trace} ({len(tr.finished_spans())} spans)",
              flush=True)
    if failed:
        print(f"# FAILED suites: {failed}", flush=True)
        sys.exit(1)
    print("# all benchmark suites completed", flush=True)


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if "--check" in sys.argv:
        check()
    else:
        trace = None
        if "--trace" in sys.argv:
            i = sys.argv.index("--trace")
            nxt = sys.argv[i + 1] if len(sys.argv) > i + 1 else ""
            trace = nxt if nxt and not nxt.startswith("-") \
                else "bench_trace.json"
        main(trace)
