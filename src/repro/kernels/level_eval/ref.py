"""jnp oracle: garble/evaluate one topological netlist level in one pass.

Per gate (op ∈ {0:XOR, 1:AND, 2:INV, 3:PAD}):
    XOR -> a ^ b              (FreeXOR)
    AND -> HalfGate(a, b, tables, tweak)
    INV -> a                  (label passes through; semantics flip
                               garbler-side)
    PAD -> 0                  (padding lane of a compiled level plan;
                               reads/writes the plan's dummy wire)
Computing the Half-Gate for every lane and masking is branch-free — the
right shape for the VPU (the paper's PE co-issues Half-Gate and FreeXOR
units; a SIMD machine evaluates both and selects). The garble lane
mirrors this for the garbler side: FreeXOR / INV-offset / Half-Gate table
generation in one fused pass, with tg/te masked to zero off the AND lanes
so padded scatters stay deterministic. (Since the packed-table-emission
overhaul the device executor hands the garble lane AND/PAD lanes only —
free-lane table rows are zero by construction and are no longer shipped
through the kernel; eval still takes the full concatenated level.)
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.halfgate import ref as HG

U32 = jnp.uint32


def eval_level(ops, a, b, tg, te, tweaks):
    """ops (G,) uint32; labels/tables (G, 4); tweaks (G,). -> (G, 4)."""
    and_out = HG.eval_and_gates(a, b, tg, te, tweaks)
    xor_out = a ^ b
    is_and = (ops == U32(1))[:, None]
    is_inv = (ops == U32(2))[:, None]
    is_pad = (ops >= U32(3))[:, None]
    out = jnp.where(is_and, and_out, xor_out)
    out = jnp.where(is_inv, a, out)
    return jnp.where(is_pad, U32(0), out)


def garble_level(ops, a0, b0, r, tweaks):
    """Garbler-side fused level pass.

    ops (G,) uint32; a0/b0/r (G, 4) zero-labels and FreeXOR offset;
    tweaks (G,). Returns (c0, tg, te), each (G, 4): the output zero-label
    plus the two Half-Gate table rows (zero off the AND lanes).
    """
    c_and, tg, te = HG.garble_and_gates(a0, b0, r, tweaks)
    is_and = (ops == U32(1))[:, None]
    is_inv = (ops == U32(2))[:, None]
    is_pad = (ops >= U32(3))[:, None]
    c0 = jnp.where(is_and, c_and, a0 ^ b0)
    c0 = jnp.where(is_inv, a0 ^ r, c0)
    c0 = jnp.where(is_pad, U32(0), c0)
    zero = jnp.zeros_like(tg)
    return (
        c0,
        jnp.where(is_and, tg, zero),
        jnp.where(is_and, te, zero),
    )


def eval_level_planar(ops, a, b, tg, te, tweaks):
    """:func:`eval_level` on word planes: labels/tables are 4-tuples of
    equal-shape uint32 planes, ``ops``/``tweaks`` of that shape too.
    Returns the 4-tuple of output planes (bit-identical per gate)."""
    and_out = HG.eval_and_split(a, b, tg, te, tweaks)
    is_and = ops == U32(1)
    is_inv = ops == U32(2)
    is_pad = ops >= U32(3)
    out = []
    for k in range(4):
        o = jnp.where(is_and, and_out[k], a[k] ^ b[k])
        o = jnp.where(is_inv, a[k], o)
        out.append(jnp.where(is_pad, U32(0), o))
    return tuple(out)


def garble_level_planar(ops, a0, b0, r, tweaks):
    """:func:`garble_level` on word planes (see :func:`eval_level_planar`).
    Returns ``(c0, tg, te)``, each a 4-tuple of planes."""
    c_and, tg, te = HG.garble_and_split(a0, b0, r, tweaks)
    is_and = ops == U32(1)
    is_inv = ops == U32(2)
    is_pad = ops >= U32(3)
    c0, tgo, teo = [], [], []
    for k in range(4):
        c = jnp.where(is_and, c_and[k], a0[k] ^ b0[k])
        c = jnp.where(is_inv, a0[k] ^ r[k], c)
        c0.append(jnp.where(is_pad, U32(0), c))
        tgo.append(jnp.where(is_and, tg[k], U32(0)))
        teo.append(jnp.where(is_and, te[k], U32(0)))
    return tuple(c0), tuple(tgo), tuple(teo)
