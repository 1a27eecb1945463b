"""Fig. 5(b): AND-gate counts of 64-bit multipliers.

conventional schoolbook vs XFBQ (without / with Q-error correction).
"""

from __future__ import annotations

from repro.core.circuits import arith
from repro.core.circuits.builder import CircuitBuilder
from benchmarks.common import emit, timeit


def counts(k: int):
    out = {}
    for style, qe in [("conventional", False), ("xfbq", False),
                      ("xfbq", True)]:
        cb = CircuitBuilder()
        a = cb.g_input_word(k)
        b = cb.e_input_word(k)
        cb.output(arith.mul(cb, a, b, style=style, qerror_terms=qe))
        out[(style, qe)] = cb.build().and_count
    return out


def main():
    for k in (16, 32, 64):
        c = counts(k)
        base = c[("conventional", False)]
        us = timeit(lambda: counts(8), n=1)
        emit(
            f"fig5b_mult{k}_conventional", us, f"ANDs={base}"
        )
        emit(
            f"fig5b_mult{k}_xfbq", us,
            f"ANDs={c[('xfbq', False)]}"
            f";reduction={100 * (1 - c[('xfbq', False)] / base):.1f}%"
            f";paper=45.5%",
        )
        emit(
            f"fig5b_mult{k}_xfbq_qerr", us,
            f"ANDs={c[('xfbq', True)]}"
            f";reduction={100 * (1 - c[('xfbq', True)] / base):.1f}%"
            f";paper=38.9%",
        )


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
