"""APINT protocol layers on shares: correctness + workload claims."""

import numpy as np
import pytest

from repro.config import PrivacyConfig
from repro.core import secret_sharing as SS
from repro.core.protocol import PiTProtocol


def _proto(frac=6, offload=True, seed=0):
    pcfg = PrivacyConfig(
        he_poly_n=256, he_num_primes=3, he_t_bits=40, frac_bits=frac,
        layernorm_offload=offload,
    )
    return PiTProtocol(pcfg, seed=seed)


@pytest.fixture(scope="module")
def P():
    return _proto()


def test_share_roundtrip(P, rng):
    x = rng.normal(0, 2, (4, 8))
    c, s = P.share_input(x)
    got = P.reveal(c, s)
    assert np.abs(got - x).max() < 2 ** -(P.frac - 1)


def test_linear_delphi(P, rng):
    W = rng.normal(0, 0.5, (6, 8))
    x = rng.normal(0, 1, 8)
    xc, xs = P.share_input(x)
    yc, ys = P.linear(W, xc, xs, use_he_offline=True)
    got = P.reveal(yc, ys, scale_bits=2 * P.frac)
    assert np.abs(got - W @ x).max() < 0.05


def test_beaver_matmul(P, rng):
    A = rng.normal(0, 1, (3, 5))
    B = rng.normal(0, 1, (5, 2))
    ac, as_ = P.share_input(A)
    bc, bs = P.share_input(B)
    zc, zs = P.matmul_private(ac, as_, bc, bs)
    got = P.reveal(zc, zs, scale_bits=2 * P.frac)
    assert np.abs(got - A @ B).max() < 0.1


def test_softmax_on_shares(P, rng):
    rows = rng.normal(0, 1.5, (3, 4))
    c, s = SS.share(rng, SS.encode_fx(rows, 2 * P.frac, P.t), P.t)
    oc, os_ = P.softmax_rows(c, s, 4, in_scale=2 * P.frac)
    got = P.reveal(oc, os_)
    want = np.exp(rows - rows.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    assert np.abs(got - want).max() < 0.05
    assert abs(got.sum(1) - 1).max() < 0.1


def test_gelu_on_shares(P, rng):
    from repro.core.circuits.nonlinear import _gelu

    x = rng.normal(0, 2, (2, 5))
    c, s = SS.share(rng, SS.encode_fx(x, 2 * P.frac, P.t), P.t)
    oc, os_ = P.activation("gelu", c, s, in_scale=2 * P.frac)
    want = np.vectorize(lambda v: _gelu(max(min(v, 4), -4)))(x)
    assert np.abs(P.reveal(oc, os_) - want).max() < 0.1


def test_layernorm_offload_matches_full(rng):
    x = rng.normal(0, 1, (2, 8))
    gamma = rng.normal(1, 0.1, 8)
    beta = rng.normal(0, 0.1, 8)
    mu = x.mean(1, keepdims=True)
    sd = np.sqrt(((x - mu) ** 2).mean(1, keepdims=True))
    want = (x - mu) / sd * gamma + beta
    outs = {}
    ands = {}
    for offload in (False, True):
        Pr = _proto(offload=offload, seed=1)
        c, s = SS.share(rng, SS.encode_fx(x, Pr.frac, Pr.t), Pr.t)
        oc, os_ = Pr.layernorm(c, s, gamma, beta, in_scale=Pr.frac)
        outs[offload] = Pr.reveal(oc, os_)
        ands[offload] = sum(v["and"] for v in Pr.stats.per_fn.values())
    assert np.abs(outs[False] - want).max() < 0.15
    assert np.abs(outs[True] - want).max() < 0.15
    # the paper's LayerNorm claim: the offload removes ~47% of GC work
    reduction = 1 - ands[True] / ands[False]
    assert 0.30 < reduction < 0.65, reduction


def test_layernorm_offload_wide_rows(rng):
    """Rows wider than 2^(frac+1), where round(2^frac / n) is 0: the
    offload centres exactly (n·x − Σx) and divides by n in the circuit
    (BERT-base's n=768 at frac=7 is this case)."""
    n = 96
    x = rng.normal(0, 1, (2, n))
    gamma = rng.normal(1, 0.1, n)
    beta = rng.normal(0, 0.1, n)
    mu = x.mean(1, keepdims=True)
    sd = np.sqrt(((x - mu) ** 2).mean(1, keepdims=True))
    want = (x - mu) / sd * gamma + beta
    Pr = _proto(frac=5, seed=1)
    assert n > 2 ** (Pr.frac + 1)
    c, s = SS.share(rng, SS.encode_fx(x, Pr.frac, Pr.t), Pr.t)
    oc, os_ = Pr.layernorm(c, s, gamma, beta, in_scale=Pr.frac)
    assert np.abs(Pr.reveal(oc, os_) - want).max() < 0.15


def test_comm_accounting(P):
    st = P.stats
    assert st.channel_offline.total > 0
    assert st.channel_online.total > 0
    assert st.gc_instances_ands > 0
    # offline carries tables + HE; online carries OT + openings
    assert any(k.startswith("tables") for k in st.channel_offline.by_tag)
    assert any(k.startswith("ot") for k in st.channel_online.by_tag)


def test_gc_truncation_exact(P, rng):
    """Deferred truncation inside GC is exact (floor division)."""
    x = rng.normal(0, 1, (1, 6))
    enc = SS.encode_fx(x, 2 * P.frac, P.t)
    c, s = SS.share(rng, enc, P.t)

    def body(cb, ins):
        return [ins[0]]

    net = P.build_fn_circuit("trunc_test", 1, 1, body, descale=P.frac)
    oc, os_ = P.gc_apply(net, c.reshape(-1, 1), s.reshape(-1, 1), 1)
    got = P.reveal(oc.reshape(1, 6), os_.reshape(1, 6))
    fx = np.round(x * (1 << 2 * P.frac))
    want = np.floor(fx / (1 << P.frac)) / (1 << P.frac)
    assert np.abs(got - want).max() < 1e-9
