"""The benchmark's own counts and its plain reference."""

import copy
import dataclasses

import numpy as np
import pytest

from bench import counts, model

TINY = {"hidden_size": 16, "num_attention_heads": 2, "head_dim": 8,
        "intermediate_size": 32, "num_hidden_layers": 2,
        "layer_norm_eps": 1e-12,
        "privacy": {"frac_bits": 7, "gelu_input_clip": 4.0}}


def test_model_flops_counts_every_matmul():
    base = {"hidden_size": 768, "intermediate_size": 3072,
            "num_hidden_layers": 1}
    macs = 8 * (4 * 768 * 768 + 2 * 768 * 3072) + 2 * 8 * 8 * 768
    assert counts.model_flops(base, 8) == 2 * macs
    assert counts.model_flops(dict(base, num_hidden_layers=12), 8) \
        == 12 * 2 * macs


def test_gc_bytes():
    assert counts.gc_bytes(10, 3, 2, 1) == 10 * 32 + 5 * 16
    assert counts.gc_bytes(10, 3, 2, 7) == 7 * (10 * 32 + 5 * 16)


def test_weights_and_inputs_follow_the_seed():
    big = 2**31 + 12345
    a = model.make_weights(TINY, big)
    b = model.make_weights(TINY, big)
    c = model.make_weights(TINY, big + 1)
    assert len(a) == TINY["num_hidden_layers"]
    for k in model.LAYER_KEYS:
        assert a[0][k].shape == model.shapes(TINY)[k]
        assert np.array_equal(a[0][k], b[0][k])
    assert not np.array_equal(a[0]["wq"], c[0]["wq"])
    x = model.make_input(TINY, 4, big, 3)
    assert np.array_equal(x, model.make_input(TINY, 4, big, 3))
    assert not np.array_equal(x, model.make_input(TINY, 4, big, 4))


def test_reference_matches_the_programs_rounded_float_model():
    """At a small width the reference equals the program's float forward
    pass over the same 7-bit rounded weights and input."""
    from repro.config import PrivacyConfig
    from repro.core.engine import BertWeights, PrivateTransformer

    f = TINY["privacy"]["frac_bits"]
    weights = model.make_weights(TINY, 5)
    pt = PrivateTransformer(PrivacyConfig(frac_bits=f), 16, 2, 32,
                            [BertWeights(**w) for w in weights])
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
    got = model.reference(TINY, weights, x, f)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-4)
    # and a coarser rounding moves it
    assert np.abs(model.reference(TINY, weights, x, f - 2) - want).max() \
        > 1e-2


@pytest.mark.parametrize("frac", [6, 7])
def test_reference_rounds_inputs(frac):
    w = model.make_weights(TINY, 1)
    x = model.make_input(TINY, 4, 1, 0)
    step = 1 << frac
    assert np.array_equal(model.reference(TINY, w, x, frac),
                          model.reference(TINY, w, np.round(x * step) / step,
                                          frac))
