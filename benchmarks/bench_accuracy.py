"""Fig. 8(a) analog: private-inference output parity vs float reference.

The paper runs GLUE; without task data we report numerical parity of the
full private pipeline (shares + HE + GC with the paper's approximations)
on a reduced transformer block — the quantity GLUE accuracy is downstream
of."""

from __future__ import annotations

import numpy as np

from repro.config import PrivacyConfig
from repro.core.engine import PrivateTransformer, random_weights
from benchmarks.common import emit, timeit


def main():
    rng = np.random.default_rng(3)
    d, heads, d_ff, S = 16, 2, 32, 8
    weights = random_weights(rng, d, d_ff, 1)
    x = rng.normal(0, 1, (S, d))
    pcfg = PrivacyConfig(he_poly_n=256, he_num_primes=3, he_t_bits=40,
                         frac_bits=7)
    eng = PrivateTransformer(pcfg, d, heads, d_ff, weights, seed=0)
    import time

    t0 = time.time()
    got = eng.forward_private(x)
    dt = time.time() - t0
    want = eng.forward_float(x)
    mae = float(np.abs(got - want).mean())
    mx = float(np.abs(got - want).max())
    st = eng.p.stats
    emit(
        "fig8a_parity", dt * 1e6,
        f"mae={mae:.4f};max={mx:.4f};paper_glue_drop=0.09pt"
        f";online_MB={st.channel_online.total / 1e6:.2f}"
        f";offline_MB={st.channel_offline.total / 1e6:.2f}",
    )


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
