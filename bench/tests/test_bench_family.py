"""A configuration brings its own architecture with files and entries
alone: a family the harness has never seen, with its own key names, runs a
whole cell on a copy of the benchmark whose harness files are the repo's,
byte for byte; a configuration whose family has no file is refused when
its cell is loaded."""

import json
import shutil
import time
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4242
#: the check's limit at this small width, as in ``test_bench_check.py``
SMALL_LIMIT = 0.09

#: a family over other key names (d_model, n_heads, ffn, n_layers) that
#: maps them onto the program's encoder; its weights (nested, in a
#: dataclass of its own), input and reference are its own
PROBE = '''
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench import model as M


@dataclasses.dataclass
class Layer:
    attn: dict
    mlp: dict
    norms: dict


def check(cfg):
    if cfg["d_model"] % cfg["n_heads"]:
        raise ValueError("d_model is not a multiple of n_heads")


def describe(cfg):
    return f"probe d_model={cfg['d_model']} ffn={cfg['ffn']}"


def make_weights(cfg, seed):
    d, f = cfg["d_model"], cfg["ffn"]
    rng = np.random.default_rng(M.seed_words(seed, 1))
    shape = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
             "w1": (f, d), "w2": (d, f)}
    out = []
    for _ in range(cfg["n_layers"]):
        w = {k: rng.normal(0.0, 1 / math.sqrt(s[1]), s)
             for k, s in shape.items()}
        norms = {}
        for k in ("ln1", "ln2"):
            norms[k + "_g"] = 1.0 + rng.normal(0.0, 0.05, d)
            norms[k + "_b"] = rng.normal(0.0, 0.05, d)
        out.append(Layer(attn={k: w[k] for k in ("wq", "wk", "wv", "wo")},
                         mlp={"w1": w["w1"], "w2": w["w2"]}, norms=norms))
    return out


def make_input(cfg, seq_len, seed, index):
    rng = np.random.default_rng([M.seed_words(seed, 1)[0], index])
    return rng.normal(0.0, 1.0, (seq_len, cfg["d_model"]))


def reference(cfg, weights, x, frac_bits):
    hd = cfg["d_model"] // cfg["n_heads"]
    eps = np.float32(cfg["eps"])
    clip = np.float32(cfg["privacy"]["gelu_input_clip"])
    erf = np.vectorize(math.erf, otypes=[np.float64])

    def rnd(a):
        return M.round_frac(a, frac_bits)

    def ln(v, g, b):
        mu = v.mean(-1, keepdims=True)
        var = ((v - mu) ** 2).mean(-1, keepdims=True)
        return (v - mu) / np.sqrt(var + eps) * g + b

    h = rnd(x)
    for layer in weights:
        w = {**layer.attn, **layer.mlp, **layer.norms}
        q = h @ rnd(w["wq"] / math.sqrt(hd)).T
        k, v = h @ rnd(w["wk"]).T, h @ rnd(w["wv"]).T
        ctx = np.zeros_like(h)
        for i in range(cfg["n_heads"]):
            sl = slice(i * hd, (i + 1) * hd)
            s = q[:, sl] @ k[:, sl].T
            e = np.exp(s - s.max(-1, keepdims=True))
            ctx[:, sl] = (e / e.sum(-1, keepdims=True)) @ v[:, sl]
        h = ln(h + ctx @ rnd(w["wo"]).T, w["ln1_g"].astype(np.float32),
               rnd(w["ln1_b"]))
        z = np.clip(h @ rnd(w["w1"]).T, -clip, clip)
        a = (0.5 * z * (1.0 + erf(z / math.sqrt(2.0)))).astype(np.float32)
        h = ln(h + a @ rnd(w["w2"]).T, w["ln2_g"].astype(np.float32),
               rnd(w["ln2_b"]))
    return h.astype(np.float32)


def flops(cfg, seq_len):
    d, f = cfg["d_model"], cfg["ffn"]
    return 2 * cfg["n_layers"] * (seq_len * (4 * d * d + 2 * d * f)
                                  + 2 * seq_len * seq_len * d)


def build(pcfg, cfg, weights, seed):
    from repro.core.engine import BertWeights, PrivateTransformer

    return PrivateTransformer(
        pcfg, cfg["d_model"], cfg["n_heads"], cfg["ffn"],
        [BertWeights(**w.attn, **w.mlp, **w.norms) for w in weights],
        seed=seed)
'''

PROBE_CONFIG = {
    "name": "probe", "source": "https://arxiv.org/abs/1810.04805",
    "family": "probe", "d_model": 16, "n_heads": 2, "ffn": 32,
    "n_layers": 1, "eps": 1e-12,
    "privacy": {"he_t_bits": 40, "frac_bits": 7, "layernorm_offload": True,
                "gelu_input_clip": 4.0},
    "pool_cap": 1, "check_limits": {"max_abs_gap": SMALL_LIMIT},
    "reduced": [],
}


def copy_with_probe(tmp_path: Path, config: dict) -> Path:
    """The repo's ``BENCHMARK.json`` and ``bench/`` copied to ``tmp_path``,
    with the probe family, ``config`` and a ``probe.online`` cell added the
    way a configuration's PR adds them: new files and new entries."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench" / "families" / "probe.py").write_text(PROBE)
    (tmp_path / "bench" / "configs" / "probe.json").write_text(
        json.dumps(config))
    (tmp_path / "bench" / "traffic" / "probe-online.json").write_text(
        json.dumps({"kind": "online", "seq_len": 4, "trace_pool": 1}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "probe", "source": config["source"],
        "file": "bench/configs/probe.json", "reduced": [],
        "why": "a family the harness has never seen"})
    bench["workloads"].append({
        "name": "probe.online", "config": "probe",
        "traffic": "probe-online", "chips": 1,
        "why": "S=4, closed loop, one client"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "bert-tiny.online" in m.get("workloads", []):
            m["workloads"].append("probe.online")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_a_new_family_runs_a_cell_with_files_and_entries_alone(tmp_path):
    root = copy_with_probe(tmp_path, PROBE_CONFIG)
    cell = harness.load_cell(root, "probe.online")
    assert cell.family.__file__ == str(
        root / "bench" / "families" / "probe.py")
    res = harness.run_cell(root, cell, SEED, 1.0, False, time.perf_counter(),
                           {"platform": "cpu", "kind": "cpu", "count": 1},
                           impl="ref")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"online_s", "online_wire_mb", "setup_s"}
    for name in ("harness.py", "counts.py", "model.py"):
        assert (root / "bench" / name).read_bytes() == \
            (ROOT / "bench" / name).read_bytes()


@pytest.mark.parametrize("family", [None, "nosuch"], ids=["no_key", "no_file"])
def test_a_config_whose_family_has_no_file_is_refused(tmp_path, family):
    config = {k: v for k, v in PROBE_CONFIG.items() if k != "family"}
    if family:
        config["family"] = family
    root = copy_with_probe(tmp_path, config)
    with pytest.raises((ValueError, FileNotFoundError),
                       match=rf"bench/families/{family or '<name>'}\.py"):
        harness.load_cell(root, "probe.online")
