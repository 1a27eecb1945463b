"""The BERT encoder family: post-norm multi-head self-attention, residual,
LayerNorm, GELU MLP, residual, LayerNorm.

Everything of a configuration that is particular to its architecture is
here: the check of its file, its log line, its weights drawn from the
seed, its plain float32 reference, its FLOP count and the builder that
hands the weights to the program. The harness treats the weights as
opaque and passes this module's own ``make_weights`` result to its
``build`` and ``reference``. Only ``build`` imports the program, inside
its body, so the reference stays independent of the code under test.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench import model as M

#: weight names of one encoder layer, in the order they are drawn
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w1", "w2",
              "ln1_g", "ln1_b", "ln2_g", "ln2_b")
#: the sizes this family reads, each a whole number of at least 1
SIZE_KEYS = ("hidden_size", "num_attention_heads", "head_dim",
             "intermediate_size", "num_hidden_layers")


def check(cfg: dict) -> None:
    """Raise ``ValueError`` on a configuration file this family cannot run."""
    for key in SIZE_KEYS:
        v = cfg.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 1:
            raise ValueError(f"bert config {cfg.get('name')!r}: {key} is "
                             f"{v!r}, not a whole number of at least 1")
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError(
            f"bert config {cfg.get('name')!r}: hidden_size "
            f"{cfg['hidden_size']} is not num_attention_heads "
            f"{cfg['num_attention_heads']} x head_dim {cfg['head_dim']}")


def describe(cfg: dict) -> str:
    """The sizes a run's log line gives."""
    return (f"d={cfg['hidden_size']} heads={cfg['num_attention_heads']} "
            f"d_ff={cfg['intermediate_size']} "
            f"layers={cfg['num_hidden_layers']}")


def shapes(cfg: dict) -> Dict[str, tuple]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "w1": (f, d), "w2": (d, f),
            "ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,)}


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

    drawn = jax.device_get(draw(jax.random.PRNGKey(M.seed_words(seed, 1)[0])))
    return [{k: np.asarray(v, np.float64) for k, v in layer.items()}
            for layer in drawn]


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
    def rnd(a):
        return M.round_frac(a, frac_bits)

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


def flops(cfg: dict, seq_len: int) -> int:
    """Floating-point operations of the plaintext forward pass over one
    (seq_len, d) input: 2 per multiply-accumulate of the four projections,
    the two MLP matmuls, QK^T and PV."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = seq_len * (4 * d * d + 2 * d * f) + 2 * seq_len * seq_len * d
    return 2 * cfg["num_hidden_layers"] * per_layer


def build(pcfg, cfg: dict, weights: List[Dict[str, np.ndarray]], seed: int):
    """The model object ``PitGateway`` serves: the program's encoder over
    these weights."""
    from repro.core.engine import BertWeights, PrivateTransformer

    return PrivateTransformer(
        pcfg, cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["intermediate_size"], [BertWeights(**w) for w in weights],
        seed=seed)
