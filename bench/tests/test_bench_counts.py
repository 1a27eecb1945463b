"""The benchmark's own counts, and the bert family's weights, plain
reference and FLOP count, pinned to the numbers they gave before they
moved into ``bench/families/bert.py``."""

import copy
import dataclasses
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import counts, harness, model

ROOT = Path(__file__).resolve().parents[2]
PINS = json.loads((Path(__file__).resolve().parent / "data" / "families"
                   / "bert.json").read_text())
TINY = PINS["tiny"]
bert = harness.load_family(ROOT, "bert")


def config(name: str) -> dict:
    return TINY if name == "tiny" else json.loads((ROOT / name).read_text())


def digest(weights) -> str:
    """SHA-256 over each layer's arrays in draw order: name, shape and
    float64 bytes."""
    h = hashlib.sha256()
    for layer in weights:
        for k, v in layer.items():
            a = np.ascontiguousarray(v, np.float64)
            h.update(k.encode())
            h.update(repr(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def test_model_flops_counts_every_matmul():
    base = {"hidden_size": 768, "intermediate_size": 3072,
            "num_hidden_layers": 1}
    macs = 8 * (4 * 768 * 768 + 2 * 768 * 3072) + 2 * 8 * 8 * 768
    assert bert.flops(base, 8) == 2 * macs
    assert bert.flops(dict(base, num_hidden_layers=12), 8) == 12 * 2 * macs


def test_gc_bytes():
    assert counts.gc_bytes(10, 3, 2, 1) == 10 * 32 + 5 * 16
    assert counts.gc_bytes(10, 3, 2, 7) == 7 * (10 * 32 + 5 * 16)


def test_weights_and_inputs_follow_the_seed():
    big = 2**31 + 12345
    a = bert.make_weights(TINY, big)
    b = bert.make_weights(TINY, big)
    c = bert.make_weights(TINY, big + 1)
    assert len(a) == TINY["num_hidden_layers"]
    for k in bert.LAYER_KEYS:
        assert a[0][k].shape == bert.shapes(TINY)[k]
        assert np.array_equal(a[0][k], b[0][k])
    assert not np.array_equal(a[0]["wq"], c[0]["wq"])
    x = model.make_input(TINY, 4, big, 3)
    assert np.array_equal(x, model.make_input(TINY, 4, big, 3))
    assert not np.array_equal(x, model.make_input(TINY, 4, big, 4))


def test_reference_matches_the_programs_rounded_float_model():
    """At a small width the reference equals the program's float forward
    pass over the same 7-bit rounded weights and input."""
    from repro.config import PrivacyConfig

    f = TINY["privacy"]["frac_bits"]
    weights = bert.make_weights(TINY, 5)
    pt = bert.build(PrivacyConfig(frac_bits=f), TINY, weights, 0)
    step = float(1 << f)

    def rnd(a):
        return np.round(a * step) / step

    ref = copy.copy(pt)
    ref.weights = [dataclasses.replace(
        w, wq=rnd(w.wq * pt.scale_q) / pt.scale_q, wk=rnd(w.wk),
        wv=rnd(w.wv), wo=rnd(w.wo), w1=rnd(w.w1), w2=rnd(w.w2),
        ln1_b=rnd(w.ln1_b), ln2_b=rnd(w.ln2_b)) for w in pt.weights]
    x = model.make_input(TINY, 4, 5, 0)
    want = ref.forward_float(rnd(x))
    got = bert.reference(TINY, weights, x, f)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-4)
    # and a coarser rounding moves it
    assert np.abs(bert.reference(TINY, weights, x, f - 2) - want).max() \
        > 1e-2


@pytest.mark.parametrize("frac", [6, 7])
def test_reference_rounds_inputs(frac):
    w = bert.make_weights(TINY, 1)
    x = model.make_input(TINY, 4, 1, 0)
    step = 1 << frac
    assert np.array_equal(bert.reference(TINY, w, x, frac),
                          bert.reference(TINY, w, np.round(x * step) / step,
                                         frac))


@pytest.mark.parametrize("pin", PINS["weights_sha256"],
                         ids=lambda p: f"{p['config']}-{p['seed']}")
def test_weights_are_the_pinned_draw(pin):
    assert digest(bert.make_weights(config(pin["config"]), pin["seed"])) \
        == pin["sha256"]


def test_reference_is_the_pinned_output():
    pin = PINS["reference"]
    cfg = config(pin["config"])
    w = bert.make_weights(cfg, pin["weights_seed"])
    x = model.make_input(cfg, pin["seq_len"], pin["input_seed"],
                         pin["input_index"])
    got = bert.reference(cfg, w, x, pin["frac_bits"])
    assert np.array_equal(got, np.asarray(pin["output"], np.float32))


@pytest.mark.parametrize("pin", PINS["flops"], ids=lambda p: p["config"])
def test_flops_are_pinned(pin):
    assert bert.flops(config(pin["config"]), pin["seq_len"]) == pin["flops"]
