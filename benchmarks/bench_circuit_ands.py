"""Fig. 9(a): per-function AND reduction from GC-friendly circuit
generation, at the paper's bit precisions (37b softmax/layernorm, 21b
GeLU). Row length 16 (per-element costs are linear in row length; the
derived column includes the BERT-base extrapolation to 128)."""

from __future__ import annotations

from repro.core.circuits import nonlinear as NL
from benchmarks.common import emit

N_ROW = 16
BERT_ROW = 128
PAPER = {"softmax": 48.1, "gelu": 33.7, "layernorm": 45.6}


def main():
    builders = {
        "softmax": lambda s: NL.softmax_circuit(N_ROW, k=37, frac=12, style=s),
        "gelu": lambda s: NL.gelu_circuit(k=21, frac=10, style=s),
        "layernorm": lambda s: NL.layernorm_full_circuit(
            N_ROW, k=37, frac=12, style=s),
    }
    for name, build in builders.items():
        conv = build("conventional").build()
        xfbq = build("xfbq").build()
        red = 100 * (1 - xfbq.and_count / conv.and_count)
        scale = BERT_ROW / N_ROW if name != "gelu" else 1.0
        emit(
            f"fig9a_{name}", 0.0,
            f"ANDs_conv={conv.and_count};ANDs_xfbq={xfbq.and_count}"
            f";reduction={red:.1f}%;paper={PAPER[name]}%"
            f";bert128_ANDs~={int(xfbq.and_count * scale)}",
        )


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
