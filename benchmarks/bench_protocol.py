"""Fig. 8(b): end-to-end BERT-base (12L, d=768, H=12, 128 tokens) offline /
online latency model across the APINT stack, plus a *measured* offline/
online split taken directly from the PiTSession phase boundary.

The analytic table is built from measured unit costs on this machine:
  * per-function AND counts from our circuit generator at the paper's bit
    precisions (row circuits built at n=8/16, per-element costs fitted
    linearly — softmax/LN costs are affine in row length);
  * CPU Half-Gate throughput from bench_kernels (numpy engine);
  * the paper's LAN model (9.6 Gb/s, 0.165 ms);
  * the accelerator speedups from the Fig. 10 cycle model.

The measured table runs a reduced model through compile → preprocess →
run: offline numbers are whatever ``session.preprocess`` metered, online
numbers are whatever ``session.run`` metered — no ad-hoc timer deltas.

Variants: PRIMER-baseline -> +APINT protocol (LN offload) ->
+GC-friendly circuits (XFBQ) -> +APINT accelerator.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.circuits import nonlinear as NL
from benchmarks.common import NET_BW_BPS, NET_LAT_S, emit
from benchmarks.bench_kernels import halfgate_throughput

L, D, H, S, DFF = 12, 768, 12, 128, 3072
KB = 37
KG = 21
TABLE_B = 32
LABEL_B = 16
OT_B = 48  # per transferred input bit (IKNP)


def _fit_row_ands(build, ns=(8, 16)):
    """ANDs(row n) ~ a*n + b."""
    xs, ys = [], []
    for n in ns:
        ys.append(build(n).build().and_count)
        xs.append(n)
    a = (ys[1] - ys[0]) / (xs[1] - xs[0])
    b = ys[0] - a * xs[0]
    return lambda n: a * n + b


@dataclass
class Workload:
    ands: float
    g_in_bits: float  # garbler input bits (labels offline)
    e_in_bits: float  # evaluator input bits (OT online)


def bert_workload(style: str, ln_offload: bool) -> Workload:
    softmax_row = _fit_row_ands(
        lambda n: NL.softmax_circuit(n, k=KB, frac=12, style=style))
    ln_full_row = _fit_row_ands(
        lambda n: NL.layernorm_full_circuit(n, k=KB, frac=12, style=style))
    ln_red_row = _fit_row_ands(
        lambda n: NL.layernorm_reduced_circuit(n, k=KB, frac=12, style=style))
    gelu = NL.gelu_circuit(k=KG, frac=10, style=style).build().and_count

    softmax_ands = L * H * S * softmax_row(S)
    gelu_ands = L * S * DFF * gelu
    ln_row = ln_red_row(D) if ln_offload else ln_full_row(D)
    ln_ands = L * 2 * S * ln_row
    total = softmax_ands + gelu_ands + ln_ands

    # share-input words entering GC per layer (both parties, k bits each):
    words = L * (H * S * S + S * DFF + 2 * S * D)
    return Workload(ands=total, g_in_bits=words * KB, e_in_bits=words * KB)


def latency(w: Workload, garble_tput: float, eval_tput: float,
            accel_speedup: float = 1.0):
    offline_comp = w.ands / garble_tput
    offline_comm = (w.ands * TABLE_B + w.g_in_bits / 8 * LABEL_B) * 8 / NET_BW_BPS
    online_comp = w.ands / eval_tput / accel_speedup
    online_comm = w.e_in_bits * OT_B * 8 / NET_BW_BPS + 50 * NET_LAT_S
    return offline_comp + offline_comm, online_comp + online_comm


def measured_phase_split(requests: int = 2, seq: int = 4, d: int = 8):
    """Offline/online split measured at the session phase boundary.

    One preprocessing batch covers ``requests`` inferences; every run is
    online-only. Times/bytes are read from the phase ledgers that the
    compile → preprocess → run lifecycle maintains.
    """
    from repro.config import PrivacyConfig
    from repro.core.engine import PrivateTransformer, random_weights

    rng = np.random.default_rng(0)
    weights = random_weights(rng, d, 2 * d, 1)
    pcfg = PrivacyConfig(he_poly_n=256, he_num_primes=3, he_t_bits=40,
                         frac_bits=6)
    model = PrivateTransformer(pcfg, d, 2, 2 * d, weights, seed=0)
    sess = model.compile_session(seq)
    bundles = sess.preprocess(requests)
    for b in bundles:
        sess.run(rng.normal(0, 1, (seq, d)), b)
    st = sess.stats
    emit(
        "phase_split_measured", st.online.t_s / requests * 1e6,
        f"requests={requests};offline_s={st.offline.t_s:.2f}"
        f";online_s_per_req={st.online.t_s / requests:.2f}"
        f";offline_MB={st.offline.channel.total / 1e6:.2f}"
        f";online_MB_per_req={st.online.channel.total / 1e6 / requests:.3f}",
    )


def main():
    g_tput = halfgate_throughput(True)
    e_tput = halfgate_throughput(False)
    variants = {
        "primer_baseline": ("conventional", False, 1.0),
        "apint_protocol": ("conventional", True, 1.0),
        "apint_circuitgen": ("xfbq", True, 1.0),
        "apint_accelerator": ("xfbq", True, 3.3),  # Fig.10 model speedup
    }
    base_off = base_on = None
    for name, (style, off, accel) in variants.items():
        w = bert_workload(style, off)
        t_off, t_on = latency(w, g_tput, e_tput, accel)
        if base_off is None:
            base_off, base_on = t_off, t_on
        emit(
            f"fig8b_{name}", t_on * 1e6,
            f"offline_s={t_off:.1f};online_s={t_on:.1f}"
            f";and_gates={w.ands:.3e}"
            f";offline_x={base_off / t_off:.2f};online_x={base_on / t_on:.2f}",
        )
    emit(
        "fig8b_paper_reference", 0.0,
        "paper_offline_x=2.2;paper_online_x=12.2",
    )
    measured_phase_split()


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
