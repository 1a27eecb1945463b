"""Pallas TPU kernel: negacyclic NTT for RNS limb primes < 2^15.

TPU adaptation (DESIGN.md §3): the jnp reference uses ~30-bit primes with
uint64 products, which TPUs lack. Production HE-on-TPU decomposes the RNS
basis into limb primes below 2^15 so every butterfly product fits int32
exactly on the VPU; this kernel implements that limb path.

Tiling: one batch-block of polynomials is resident in VMEM ((BLOCK, N)
int32 — N=4096 is 16 KiB/row, far under VMEM); all log2(N) stages run
in-kernel (the Longa–Naehrig layout keeps every stage a contiguous
(m, 2t) reshape + concat, no gathers), so HBM sees exactly one read and
one write per polynomial per direction. Batch blocks stream through the
grid with Pallas double-buffering.

Validated in interpret mode against the jnp oracle for every (N, q) in the
test sweep.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ntt.ref import ntt_tables

DEFAULT_BLOCK = 8
_Z = np.int32(0)  # index-map constant: Mosaic wants i32 block indices


def _mulmod(a, b, q):
    return (a * b) % jnp.int32(q)


def _addmod(a, b, q):
    s = a + b
    return jnp.where(s >= jnp.int32(q), s - jnp.int32(q), s)


def _submod(a, b, q):
    d = a - b
    return jnp.where(d < 0, d + jnp.int32(q), d)


def _fwd_kernel(n, q, a_ref, psi_ref, o_ref):
    a = a_ref[...]  # (blk, n) int32
    psi = psi_ref[...]  # (1, n)
    blk = a.shape[0]
    t = n
    m = 1
    while m < n:
        t //= 2
        a = a.reshape(blk, m, 2 * t)
        u = a[..., :t]
        v = a[..., t:]
        s = jax.lax.dynamic_slice(psi, (0, m), (1, m))  # (1, m)
        v = _mulmod(v, s[0][None, :, None], q)
        a = jnp.concatenate([_addmod(u, v, q), _submod(u, v, q)], axis=-1)
        m *= 2
    o_ref[...] = a.reshape(blk, n)


def _inv_kernel(n, q, n_inv, a_ref, ipsi_ref, o_ref):
    a = a_ref[...]
    ipsi = ipsi_ref[...]
    blk = a.shape[0]
    t = 1
    m = n
    while m > 1:
        h = m // 2
        a = a.reshape(blk, h, 2 * t)
        u = a[..., :t]
        v = a[..., t:]
        s = jax.lax.dynamic_slice(ipsi, (0, h), (1, h))
        nu = _addmod(u, v, q)
        nv = _mulmod(_submod(u, v, q), s[0][None, :, None], q)
        a = jnp.concatenate([nu, nv], axis=-1)
        t *= 2
        m = h
    o_ref[...] = _mulmod(a.reshape(blk, n), jnp.int32(n_inv), q)


@functools.partial(
    jax.jit, static_argnames=("q", "n", "inverse", "block", "interpret")
)
def ntt_pallas(a, q: int, n: int, *, inverse: bool = False,
               block: int = DEFAULT_BLOCK, interpret: bool = False):
    """a: (..., N) int32/uint32 residues of a prime q < 2^15."""
    assert q <= 46340, "limb kernel needs q^2 < 2^31 (exact int32 products)"
    psi_br, ipsi_br, n_inv = ntt_tables(q, n)
    lead = a.shape[:-1]
    af = a.reshape(-1, n).astype(jnp.int32)
    b = af.shape[0]
    pad = (-b) % block
    if pad:
        af = jnp.concatenate([af, jnp.zeros((pad, n), jnp.int32)])
    bp = af.shape[0]
    table = jnp.asarray(
        (ipsi_br if inverse else psi_br).astype(np.int64), jnp.int32
    ).reshape(1, n)
    kern = (
        functools.partial(_inv_kernel, n, q, int(n_inv))
        if inverse
        else functools.partial(_fwd_kernel, n, q)
    )
    out = pl.pallas_call(
        kern,
        grid=(bp // block,),
        in_specs=[
            pl.BlockSpec((block, n), lambda i: (i, _Z)),
            pl.BlockSpec((1, n), lambda i: (_Z, _Z)),
        ],
        out_specs=pl.BlockSpec((block, n), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((bp, n), jnp.int32),
        interpret=interpret,
    )(af, table)
    out = out[:b].reshape(*lead, n)
    return out.astype(a.dtype)
