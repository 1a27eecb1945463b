"""Pallas TPU kernel: fused level-synchronous GC evaluation.

One launch per netlist level: every gate of the level streams through VMEM
together with its table rows, an op code and a tweak; the kernel computes
FreeXOR and Half-Gate lanes branch-free and selects by op. Compared with
dispatching separate XOR / AND batches this halves the DMA passes over the
level and removes the gather/scatter between them — the TPU counterpart of
the paper's single pipelined PE that co-issues Half-Gate (18 cy) and
FreeXOR (1 cy) units.

Layout: labels and table rows arrive as four lane-dense 32-bit word planes,
``(4, G)`` — the executor's planar wire store, flattened. The wrapper folds
the gate axis into ``(rows, 128)`` so each grid step sees ``(4, BR, 128)``
blocks whose every op fills whole vregs. (A ``(G, 4)`` packed tile pads 4
words to 128 lanes: 32x the VMEM, minutes of Mosaic compile, and a garble
kernel that overflows the scoped-VMEM budget at real block sizes.)

Grid streams gate blocks (double-buffered); all operands are sequential so
the DMA engine prefetches block i+1 during the cipher of block i — the
OoRW-prefetch idea at the DMA level (DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.level_eval import ref

#: gates per grid step (a multiple of 8 x 128: whole uint32 vreg tiles)
DEFAULT_BLOCK = 2048
LANES = 128
U32 = jnp.uint32
_Z = np.int32(0)  # index-map constant: Mosaic wants i32 block indices


def _kernel(ops_ref, tw_ref, a_ref, b_ref, tg_ref, te_ref, out_ref):
    planes = lambda r: tuple(r[k] for k in range(4))
    out = ref.eval_level_planar(
        ops_ref[...], planes(a_ref), planes(b_ref), planes(tg_ref),
        planes(te_ref), tw_ref[...])
    for k in range(4):
        out_ref[k] = out[k]


def _garble_kernel(ops_ref, tw_ref, a_ref, b_ref, r_ref,
                   c_ref, tg_ref, te_ref):
    planes = lambda r: tuple(r[k] for k in range(4))
    c0, tg, te = ref.garble_level_planar(
        ops_ref[...], planes(a_ref), planes(b_ref), planes(r_ref),
        tw_ref[...])
    for k in range(4):
        c_ref[k] = c0[k]
        tg_ref[k] = tg[k]
        te_ref[k] = te[k]


def _block_rows(g: int, block: int) -> int:
    """Rows of 128 gates per grid step: ``block`` gates, or fewer (but a
    whole 8-row tile) when the level is smaller."""
    rows = -(-g // LANES)
    return min(block // LANES, max(8, -(-rows // 8) * 8))


def _fold(x, rows):
    """(..., G) -> (..., rows, 128), zero-padded along the gate axis."""
    g = x.shape[-1]
    p = rows * LANES - g
    if p:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p)])
    return x.reshape(*x.shape[:-1], rows, LANES)


def _call(kernel, n_out, ops, tweaks, planes, block, interpret):
    g = ops.shape[0]
    br = _block_rows(g, block)
    rows = -(-g // (br * LANES)) * br
    word = pl.BlockSpec((br, LANES), lambda i: (i, _Z))
    plane = pl.BlockSpec((4, br, LANES), lambda i: (_Z, i, _Z))
    outs = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[word, word] + [plane] * len(planes),
        out_specs=[plane] * n_out,
        out_shape=[jax.ShapeDtypeStruct((4, rows, LANES), U32)] * n_out,
        interpret=interpret,
    )(_fold(ops.astype(U32), rows), _fold(tweaks.astype(U32), rows),
      *(_fold(x.astype(U32), rows) for x in planes))
    return [o.reshape(4, rows * LANES)[:, :g] for o in outs]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def eval_level_pallas(ops, a, b, tg, te, tweaks, *, block=DEFAULT_BLOCK,
                      interpret=False):
    """ops (G,); a/b/tg/te (4, G) word planes; tweaks (G,).

    Returns the (4, G) uint32 output label planes.
    """
    (out,) = _call(_kernel, 1, ops, tweaks, (a, b, tg, te), block,
                   interpret)
    return out


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def garble_level_pallas(ops, a0, b0, r, tweaks, *, block=DEFAULT_BLOCK,
                        interpret=False):
    """Garbler lane: ops (G,); a0/b0/r (4, G) word planes; tweaks (G,).

    Returns (c0, tg, te), each (4, G) uint32 — the fused FreeXOR / INV /
    Half-Gate garbling pass over one padded level. The device executor
    feeds this lane the AND block ONLY (ops are AND/PAD): free lanes
    have all-zero table rows by construction, and shipping them through
    a 3-output kernel tripled the garble lane's write volume for
    nothing — the executor computes their XOR/INV-offset labels inline
    and keeps this kernel's DMA budget for rows that exist.
    """
    c0, tg, te = _call(_garble_kernel, 3, ops, tweaks, (a0, b0, r), block,
                       interpret)
    return c0, tg, te
