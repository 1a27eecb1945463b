"""GC/HE kernel micro-benchmarks (CPU). The measured throughputs feed the
end-to-end protocol latency model (bench_protocol)."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.halfgate import ref_np as HN
from repro.kernels.ntt import ref as NR
from benchmarks.common import emit, timeit

_CACHE = {}


def halfgate_throughput(garbling: bool = True, n: int = 1 << 18) -> float:
    """AND gates per second on this CPU (numpy path used by the engine)."""
    key = ("hg", garbling, n)
    if key in _CACHE:
        return _CACHE[key]
    rng = np.random.default_rng(0)
    a0 = rng.integers(0, 2**32, (n, 4), dtype=np.uint32)
    b0 = rng.integers(0, 2**32, (n, 4), dtype=np.uint32)
    r = rng.integers(0, 2**32, (n, 4), dtype=np.uint32)
    tw = np.arange(n, dtype=np.uint32)
    if garbling:
        fn = lambda: HN.garble_and_gates(a0, b0, r, tw)
    else:
        _, tg, te = HN.garble_and_gates(a0, b0, r, tw)
        fn = lambda: HN.eval_and_gates(a0, b0, tg, te, tw)
    us = timeit(fn, n=3)
    _CACHE[key] = n / (us / 1e6)
    return _CACHE[key]


def main():
    for garbling in (True, False):
        tput = halfgate_throughput(garbling)
        emit(
            f"kernel_halfgate_{'garble' if garbling else 'eval'}",
            (1 << 18) / tput * 1e6,
            f"and_gates_per_s={tput:.3e}",
        )
    # NTT (BFV path, 30-bit prime, N=2048)
    n = 2048
    q = NR.find_ntt_primes(30, 1, n)[0]
    a = jnp.asarray(
        np.random.default_rng(0).integers(0, q, (8, n)).astype(np.uint64))
    f = jax.jit(lambda x: NR.ntt_forward(x, q, n))
    f(a).block_until_ready()
    us = timeit(lambda: f(a).block_until_ready(), n=5)
    emit("kernel_ntt2048_x8", us, f"ntts_per_s={8 / (us / 1e6):.1f}")
    # label_select
    from repro.kernels.label_select import ref as LR

    g = 1 << 18
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    w0 = jax.random.bits(ks[0], (g, 4), dtype=jnp.uint32)
    r = jax.random.bits(ks[1], (g, 4), dtype=jnp.uint32)
    bits = jax.random.bits(ks[2], (g,), dtype=jnp.uint32) & 1
    sel = jax.jit(LR.select_labels)
    sel(w0, r, bits).block_until_ready()
    us = timeit(lambda: sel(w0, r, bits).block_until_ready(), n=5)
    emit("kernel_label_select", us, f"labels_per_s={g / (us / 1e6):.3e}")


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
