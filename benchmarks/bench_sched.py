"""Fig. 10: latency / stall / OoRW / DRAM-access breakdown for GC
evaluation of the nonlinear functions across scheduling + speculation +
accelerator variants (HAAC baseline -> +coarse -> +fine -> APINT).

Netlists are reduced-size rows (row 8 at 24b instead of 128 at 37b) so the
cycle simulation stays CPU-tractable; the derived metrics are the paper's
*relative* claims, which are size-stable.
"""

from __future__ import annotations

from repro.accel.sim import AccelConfig, simulate_core
from repro.core.circuits import nonlinear as NL
from repro.sched import schedulers as SC
from repro.sched.speculation import speculate
from benchmarks.common import emit

CAP = 1024  # wire-memory capacity (labels) for the reduced netlists


def run_function(name: str, net):
    sr = SC.segment_reorder(net, CAP // 2)
    fine = SC.fine_grained_order(net, CAP // 2)
    variants = [
        ("haac", sr, "haac", False),
        ("coarse", sr, "haac", True),
        ("fine", fine, "haac", True),
        ("apint", fine, "apint", True),
    ]
    res = {}
    for vname, order, policy, coal in variants:
        prog = speculate(net, order, CAP, policy=policy)
        cfg = AccelConfig(coalesced=coal)
        res[vname] = simulate_core(net, prog, cfg, cfg.dram_burst_latency)
    base = res["haac"]
    ap = res["apint"]
    for vname, r in res.items():
        emit(
            f"fig10_{name}_{vname}", 0.0,
            f"cycles={r.cycles};pipe_stall={r.pipeline_stall_cycles}"
            f";mem_stall={r.memory_stall_cycles};oorw={r.oorw_count}"
            f";dram_accesses={r.dram_accesses}",
        )
    emit(
        f"fig10_{name}_summary", 0.0,
        f"speedup_vs_haac={base.cycles / ap.cycles:.2f}x"
        f";mem_stall_reduction={100 * (1 - ap.memory_stall_cycles / max(base.memory_stall_cycles, 1)):.1f}%"
        f";paper_speedup={'5.0x softmax / 2.2x gelu / 3.9x layernorm'}"
        f";paper_memstall=86.1-99.4%",
    )
    return res


def main():
    nets = {
        "softmax": NL.softmax_circuit(8, k=24, frac=8).build(),
        "gelu": NL.gelu_circuit(k=21, frac=10).build(),
        "layernorm": NL.layernorm_full_circuit(8, k=24, frac=8).build(),
    }
    out = {}
    for name, net in nets.items():
        out[name] = run_function(name, net)
    return out


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
