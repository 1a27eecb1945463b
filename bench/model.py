"""What every family of configurations shares: seeds, the client's input
and the protocol's fixed-point rounding.

Nothing here imports the program under test. The architecture itself
(weights, plain reference, FLOP count, builder) lives in
``bench/families/<family>.py``.
"""

from __future__ import annotations

from typing import List

import numpy as np


def seed_words(seed: int, n: int) -> List[int]:
    """``n`` 31-bit integers derived from a seed of any size."""
    return [int(w) & 0x7FFFFFFF
            for w in np.random.SeedSequence(seed).generate_state(n)]


def make_input(cfg: dict, seq_len: int, seed: int, index: int) -> np.ndarray:
    """Request ``index``'s (seq_len, d) client embeddings, N(0, 1)."""
    rng = np.random.default_rng([seed_words(seed, 1)[0], index])
    return rng.normal(0.0, 1.0, (seq_len, cfg["hidden_size"]))


def round_frac(a, frac_bits: int) -> np.ndarray:
    """``a`` rounded to ``frac_bits`` fractional bits, as the protocol
    encodes it, as float32."""
    step = float(1 << frac_bits)
    return (np.round(np.asarray(a, np.float64) * step) / step
            ).astype(np.float32)
