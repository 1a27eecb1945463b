"""Fused level-evaluator kernel: oracle equality + end-to-end semantic
correctness against a full garbled circuit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.circuits import arith
from repro.core.circuits.builder import CircuitBuilder
from repro.core.garble import garble, encode_inputs, const_labels, decode_outputs
from repro.core.netlist import OP_INV
from repro.kernels.halfgate import ref as HG
from repro.kernels.level_eval import ref as LE
from repro.kernels.level_eval.level_eval import (
    eval_level_pallas,
    garble_level_pallas,
)


@pytest.mark.parametrize("g", [5, 128, 3000])
def test_fused_matches_oracle(g):
    ks = jax.random.split(jax.random.PRNGKey(g), 5)
    a = jax.random.bits(ks[0], (g, 4), dtype=jnp.uint32)
    b = jax.random.bits(ks[1], (g, 4), dtype=jnp.uint32)
    tg = jax.random.bits(ks[2], (g, 4), dtype=jnp.uint32)
    te = jax.random.bits(ks[3], (g, 4), dtype=jnp.uint32)
    ops = jax.random.randint(ks[4], (g,), 0, 3).astype(jnp.uint32)
    tw = jnp.arange(g, dtype=jnp.uint32)
    want = LE.eval_level(ops, a, b, tg, te, tw)
    got = eval_level_pallas(ops, a.T, b.T, tg.T, te.T, tw, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got).T)


@pytest.mark.parametrize("g", [5, 3000])
def test_fused_garble_matches_oracle(g):
    ks = jax.random.split(jax.random.PRNGKey(100 + g), 4)
    a0 = jax.random.bits(ks[0], (g, 4), dtype=jnp.uint32)
    b0 = jax.random.bits(ks[1], (g, 4), dtype=jnp.uint32)
    r = jax.random.bits(ks[2], (g, 4), dtype=jnp.uint32) | jnp.uint32(1)
    ops = jax.random.randint(ks[3], (g,), 0, 4).astype(jnp.uint32)
    tw = jnp.arange(g, dtype=jnp.uint32)
    want = LE.garble_level(ops, a0, b0, r, tw)
    got = garble_level_pallas(ops, a0.T, b0.T, r.T, tw, interpret=True)
    for w, o in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(o).T)


def test_fused_level_evaluates_circuit(rng):
    """Walk a real garbled adder level-by-level with the fused kernel and
    decode the correct sum."""
    k = 8
    cb = CircuitBuilder()
    wa = cb.g_input_word(k)
    wb = cb.e_input_word(k)
    cb.output(arith.add(cb, wa, wb))
    net = cb.build()
    I = 1
    gc = garble(net, jax.random.PRNGKey(3), I, impl="ref")
    av, bv = int(rng.integers(0, 256)), int(rng.integers(0, 256))
    bits = lambda v: np.array([[(v >> i) & 1 for i in range(k)]])
    active = {}
    lab = encode_inputs(gc, net.garbler_inputs, bits(av))
    for j, w in enumerate(net.garbler_inputs):
        active[int(w)] = lab[:, j]
    lab = encode_inputs(gc, net.evaluator_inputs, bits(bv))
    for j, w in enumerate(net.evaluator_inputs):
        active[int(w)] = lab[:, j]
    active.update(const_labels(gc))

    wires = np.zeros((net.num_wires, 4), np.uint32)
    for w, v in active.items():
        wires[int(w)] = np.asarray(v)[0]
    and_idx = net.and_gate_index()
    tables = np.asarray(gc.tables)[0]
    for lvl in net.levels():
        ops = jnp.asarray(net.op[lvl], jnp.uint32)
        a = jnp.asarray(wires[net.in0[lvl]])
        b = jnp.asarray(wires[net.in1[lvl]])
        slots = np.where(net.op[lvl] == 1, and_idx[lvl], 0)
        tg = jnp.asarray(tables[slots, 0])
        te = jnp.asarray(tables[slots, 1])
        tw = jnp.asarray(slots, jnp.uint32)
        out = eval_level_pallas(ops, a.T, b.T, tg.T, te.T, tw,
                                interpret=True)
        wires[net.out[lvl]] = np.asarray(out).T
    out_lab = jnp.asarray(wires[net.outputs])[None]
    got_bits = decode_outputs(gc, out_lab)[0]
    got = sum(int(x) << i for i, x in enumerate(got_bits))
    assert got == (av + bv) % (1 << k)
