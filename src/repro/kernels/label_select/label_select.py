"""Pallas TPU kernel for active-label encoding (input garbling).

Tiles (BLOCK, 4) uint32 label rows through VMEM; bits ride as a (BLOCK, 1)
sidecar. Purely bandwidth-bound — the BlockSpec streaming (sequential grid,
double-buffered DMA) is the whole optimization.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 4096
U32 = jnp.uint32
_Z = np.int32(0)  # index-map constant: Mosaic wants i32 block indices


def _kernel(w0_ref, r_ref, bits_ref, out_ref):
    w0 = w0_ref[...]
    r = r_ref[...]
    bits = bits_ref[...][:, 0]
    mask = (-(bits.astype(U32)))[:, None]
    out_ref[...] = w0 ^ (r & mask)


def _pad(x, block):
    g = x.shape[0]
    p = (-g) % block
    if p:
        x = jnp.concatenate([x, jnp.zeros((p, *x.shape[1:]), x.dtype)])
    return x


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def select_labels_pallas(w0, r, bits, *, block=DEFAULT_BLOCK, interpret=False):
    """w0, r: (G, 4) uint32; bits: (G,) uint32 -> (G, 4)."""
    g = w0.shape[0]
    blk = min(block, max(8, 1 << (g - 1).bit_length()))
    w0p = _pad(w0, blk)
    rp = _pad(r, blk)
    bp = _pad(bits.reshape(-1, 1).astype(U32), blk)
    gp = w0p.shape[0]
    out = pl.pallas_call(
        _kernel,
        grid=(gp // blk,),
        in_specs=[
            pl.BlockSpec((blk, 4), lambda i: (i, _Z)),
            pl.BlockSpec((blk, 4), lambda i: (i, _Z)),
            pl.BlockSpec((blk, 1), lambda i: (i, _Z)),
        ],
        out_specs=pl.BlockSpec((blk, 4), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((gp, 4), U32),
        interpret=interpret,
    )(w0p, rp, bp)
    return out[:g]
