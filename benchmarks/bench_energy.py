"""Fig. 11(b): system energy (on-chip + external-memory-access) for HAAC
vs APINT while evaluating the nonlinear functions."""

from __future__ import annotations

from repro.accel.energy import energy_report
from repro.accel.sim import AccelConfig, simulate_core
from repro.core.circuits import nonlinear as NL
from repro.sched import schedulers as SC
from repro.sched.speculation import speculate
from benchmarks.common import emit

CAP = 1024
PAPER = {"softmax": 4.9, "gelu": 3.6, "layernorm": 5.7}


def main():
    nets = {
        "softmax": NL.softmax_circuit(8, k=24, frac=8).build(),
        "gelu": NL.gelu_circuit(k=21, frac=10).build(),
        "layernorm": NL.layernorm_full_circuit(8, k=24, frac=8).build(),
    }
    for name, net in nets.items():
        other = net.num_gates - net.and_count
        sr = SC.segment_reorder(net, CAP // 2)
        fine = SC.fine_grained_order(net, CAP // 2)
        haac = simulate_core(
            net, speculate(net, sr, CAP, policy="haac"),
            AccelConfig(coalesced=False), AccelConfig().dram_burst_latency,
        )
        apint = simulate_core(
            net, speculate(net, fine, CAP, policy="apint"),
            AccelConfig(coalesced=True), AccelConfig().dram_burst_latency,
        )
        e_haac = energy_report(haac, net.and_count, other)
        e_apint = energy_report(apint, net.and_count, other)
        ratio = e_haac["total_uj"] / e_apint["total_uj"]
        emit(
            f"fig11b_{name}", 0.0,
            f"haac_uj={e_haac['total_uj']:.1f}(ema {100*e_haac['ema_fraction']:.0f}%)"
            f";apint_uj={e_apint['total_uj']:.1f}(ema {100*e_apint['ema_fraction']:.0f}%)"
            f";saving={ratio:.2f}x;paper={PAPER[name]}x",
        )


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
