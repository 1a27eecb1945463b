"""Pallas TPU kernel: batched Half-Gate garbling / evaluation.

The GC hot loop is embarrassingly parallel over gates × instances: 128-bit
labels (uint32×4 lanes) through an ARX permutation — pure VPU work (adds,
xors, rotates). Tiling: gates stream through VMEM in (BLOCK, 4) tiles; the
FreeXOR offset R rides along as a (1, 4) broadcast block. One grid step
garbles/evaluates BLOCK gates; the DMA of tile i+1 overlaps the cipher of
tile i (Pallas double-buffers sequential grid dims) — the TPU analogue of
the paper's OoRW prefetch buffer (DESIGN.md §3).

The in-kernel math *is* the jnp oracle (`ref.py`) applied to VMEM tiles, so
kernel-vs-ref equality tests validate indexing/tiling, not a re-derivation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.halfgate import ref

DEFAULT_BLOCK = 2048
U32 = jnp.uint32
_Z = np.int32(0)  # index-map constant: Mosaic wants i32 block indices


def _pad_gates(x, block):
    g = x.shape[0]
    pad = (-g) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
    return x


def _garble_kernel(a0_ref, b0_ref, r_ref, tw_ref, c0_ref, tg_ref, te_ref):
    a0 = a0_ref[...]
    b0 = b0_ref[...]
    r = r_ref[...]  # (BLOCK, 4): per-gate R (per-instance FreeXOR offsets)
    tw = tw_ref[...][:, 0]
    c0, tg, te = ref.garble_and_gates(a0, b0, r, tw)
    c0_ref[...] = c0
    tg_ref[...] = tg
    te_ref[...] = te


def _eval_kernel(a_ref, b_ref, tg_ref, te_ref, tw_ref, c_ref):
    a = a_ref[...]
    b = b_ref[...]
    tw = tw_ref[...][:, 0]
    c_ref[...] = ref.eval_and_gates(a, b, tg_ref[...], te_ref[...], tw)


def _label_spec(block):
    return pl.BlockSpec((block, 4), lambda i: (i, _Z))


def _tweak_spec(block):
    return pl.BlockSpec((block, 1), lambda i: (i, _Z))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def garble_pallas(a0, b0, r, tweaks, *, block=DEFAULT_BLOCK, interpret=False):
    """a0,b0,r: (G,4) uint32 (r per-gate — batched instances carry their own
    FreeXOR offset); tweaks: (G,) uint32.

    Returns (c0, tg, te) each (G, 4) uint32.
    """
    g = a0.shape[0]
    blk = min(block, max(8, 1 << (g - 1).bit_length()))
    a0p = _pad_gates(a0, blk)
    b0p = _pad_gates(b0, blk)
    rp = _pad_gates(r, blk)
    twp = _pad_gates(tweaks.reshape(-1, 1), blk)
    gp = a0p.shape[0]
    out_sds = [jax.ShapeDtypeStruct((gp, 4), U32)] * 3
    c0, tg, te = pl.pallas_call(
        _garble_kernel,
        grid=(gp // blk,),
        in_specs=[
            _label_spec(blk),
            _label_spec(blk),
            _label_spec(blk),
            _tweak_spec(blk),
        ],
        out_specs=[_label_spec(blk)] * 3,
        out_shape=out_sds,
        interpret=interpret,
    )(a0p, b0p, rp, twp)
    return c0[:g], tg[:g], te[:g]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def eval_pallas(a, b, tg, te, tweaks, *, block=DEFAULT_BLOCK, interpret=False):
    """Active labels + table rows -> output labels, (G, 4) uint32."""
    g = a.shape[0]
    blk = min(block, max(8, 1 << (g - 1).bit_length()))
    ap = _pad_gates(a, blk)
    bp = _pad_gates(b, blk)
    tgp = _pad_gates(tg, blk)
    tep = _pad_gates(te, blk)
    twp = _pad_gates(tweaks.reshape(-1, 1), blk)
    gp = ap.shape[0]
    c = pl.pallas_call(
        _eval_kernel,
        grid=(gp // blk,),
        in_specs=[
            _label_spec(blk),
            _label_spec(blk),
            _label_spec(blk),
            _label_spec(blk),
            _tweak_spec(blk),
        ],
        out_specs=_label_spec(blk),
        out_shape=jax.ShapeDtypeStruct((gp, 4), U32),
        interpret=interpret,
    )(ap, bp, tgp, tep, twp)
    return c[:g]
