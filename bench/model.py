"""Weights, inputs and the plain reference of a benchmark configuration.

Nothing here imports the program under test. The weights and inputs are
drawn from the run's seed; the reference is a float32 forward pass of the
post-norm BERT encoder that the configuration file describes, with the
same rounding of inputs and weights that the protocol's fixed-point model
applies (``privacy.frac_bits`` fractional bits).
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

#: weight names of one encoder layer, in the order they are drawn
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2",
              "ln1_g", "ln1_b", "ln2_g", "ln2_b")


def shapes(cfg: dict) -> Dict[str, tuple]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "w1": (f, d), "w2": (d, f),
            "ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,)}


def seed_words(seed: int, n: int) -> List[int]:
    """``n`` 31-bit integers derived from a seed of any size."""
    return [int(w) & 0x7FFFFFFF
            for w in np.random.SeedSequence(seed).generate_state(n)]


def make_weights(cfg: dict, seed: int) -> List[Dict[str, np.ndarray]]:
    """Every layer's weights, drawn on the default device in one jitted
    call from ``seed`` (float32), returned as float64 host arrays: the
    type the program quantizes from."""
    import jax
    import jax.numpy as jnp

    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    shp = shapes(cfg)
    layers = cfg["num_hidden_layers"]
    scale = {"wq": 1 / math.sqrt(d), "wk": 1 / math.sqrt(d),
             "wv": 1 / math.sqrt(d), "wo": 1 / math.sqrt(d),
             "w1": 1 / math.sqrt(d), "w2": 1 / math.sqrt(f),
             "ln1_g": 0.05, "ln1_b": 0.05, "ln2_g": 0.05, "ln2_b": 0.05}
    mean = {k: (1.0 if k.endswith("_g") else 0.0) for k in LAYER_KEYS}

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, layers * len(LAYER_KEYS))
        out = []
        for li in range(layers):
            out.append({
                k: mean[k] + scale[k] * jax.random.normal(
                    keys[li * len(LAYER_KEYS) + j], shp[k], jnp.float32)
                for j, k in enumerate(LAYER_KEYS)})
        return out

    drawn = jax.device_get(draw(jax.random.PRNGKey(seed_words(seed, 1)[0])))
    return [{k: np.asarray(v, np.float64) for k, v in layer.items()}
            for layer in drawn]


def make_input(cfg: dict, seq_len: int, seed: int, index: int) -> np.ndarray:
    """Request ``index``'s (seq_len, d) client embeddings, N(0, 1)."""
    rng = np.random.default_rng([seed_words(seed, 1)[0], index])
    return rng.normal(0.0, 1.0, (seq_len, cfg["hidden_size"]))


def _erf(x: np.ndarray) -> np.ndarray:
    return np.vectorize(math.erf, otypes=[np.float64])(x)


def reference(cfg: dict, weights: List[Dict[str, np.ndarray]],
              x: np.ndarray, frac_bits: int) -> np.ndarray:
    """Float32 forward pass of the encoder over ``x`` (S, d).

    Inputs, projection weights (the query weights after their
    1/sqrt(head_dim) scale) and LayerNorm biases are rounded to
    ``frac_bits`` fractional bits, as the protocol encodes them; LayerNorm
    gains stay float. GELU is the erf form on inputs clipped to
    ``privacy.gelu_input_clip``; softmax and LayerNorm are exact.
    """
    step = float(1 << frac_bits)

    def rnd(a):
        return (np.round(np.asarray(a, np.float64) * step) / step
                ).astype(np.float32)

    heads = cfg["num_attention_heads"]
    hd = cfg["head_dim"]
    eps = np.float32(cfg["layer_norm_eps"])
    clip = np.float32(cfg["privacy"]["gelu_input_clip"])
    sq = 1.0 / math.sqrt(hd)

    def ln(v, g, b):
        mu = v.mean(-1, keepdims=True)
        var = ((v - mu) ** 2).mean(-1, keepdims=True)
        return (v - mu) / np.sqrt(var + eps) * g + b

    h = rnd(x)
    for w in weights:
        q = h @ rnd(w["wq"] * sq).T
        k = h @ rnd(w["wk"]).T
        v = h @ rnd(w["wv"]).T
        ctx = np.zeros_like(h)
        for i in range(heads):
            sl = slice(i * hd, (i + 1) * hd)
            s = q[:, sl] @ k[:, sl].T
            e = np.exp(s - s.max(-1, keepdims=True))
            ctx[:, sl] = (e / e.sum(-1, keepdims=True)) @ v[:, sl]
        h = ln(h + ctx @ rnd(w["wo"]).T,
               w["ln1_g"].astype(np.float32), rnd(w["ln1_b"]))
        z = np.clip(h @ rnd(w["w1"]).T, -clip, clip)
        a = (0.5 * z * (1.0 + _erf(z / math.sqrt(2.0)))).astype(np.float32)
        h = ln(h + a @ rnd(w["w2"]).T,
               w["ln2_g"].astype(np.float32), rnd(w["ln2_b"]))
    return h.astype(np.float32)
