"""Gradient compression: int8 ring reduce-scatter/all-gather with error
feedback.

The all-reduce of data-parallel gradients dominates cross-pod traffic; the
classic remedy is to quantize the payload and carry the quantization error
into the next step (error feedback keeps convergence). Implemented as an
explicit ring over ``lax.ppermute`` inside shard_map so the wire format is
truly int8 (+ one f32 scale per tensor chunk) — a 4x wire reduction vs f32.

``compressed_psum(x, axis, mesh)`` is a drop-in for ``lax.psum`` on the
named data axis; ``ErrorFeedback`` holds per-leaf residuals.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.utils.compat import shard_map


def _quant(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q, scale):
    return q.astype(jnp.float32) * scale


def _ring_allreduce_int8(x, axis_name: str):
    """Inside shard_map: reduce-scatter + all-gather rings, int8 payload."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    # reduce-scatter ring: after n-1 steps, rank r holds the full sum of
    # chunk (r+1) mod n
    def rs_step(s, acc_chunks):
        send_idx = (idx - s) % n
        payload, scale = _quant(acc_chunks[send_idx])
        payload = jax.lax.ppermute(
            payload, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        scale = jax.lax.ppermute(
            scale, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        recv_idx = (idx - s - 1) % n
        return acc_chunks.at[recv_idx].add(_dequant(payload, scale))

    acc = chunks
    for s in range(n - 1):
        acc = rs_step(s, acc)
    mine = (idx + 1) % n
    my_chunk, my_scale = _quant(acc[mine])

    # all-gather ring of the reduced chunks
    out = jnp.zeros_like(acc)
    out = out.at[mine].set(_dequant(my_chunk, my_scale))
    payload, scale, src = my_chunk, my_scale, mine
    for s in range(n - 1):
        payload = jax.lax.ppermute(
            payload, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        scale = jax.lax.ppermute(
            scale, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        src = (src - 1) % n
        out = out.at[src].set(_dequant(payload, scale))
    res = out.reshape(-1)
    if pad:
        res = res[:-pad]
    return res.reshape(x.shape)


def compressed_psum(x, axis_name: str, mesh):
    """int8 ring all-reduce of a replicated-along-axis array."""
    fn = partial(_ring_allreduce_int8, axis_name=axis_name)
    other = tuple(a for a in mesh.axis_names if a != axis_name)
    spec = P()  # replicated input/output w.r.t. all axes
    return shard_map(
        fn, mesh=mesh, in_specs=spec, out_specs=spec, check=False
    )(x)


class ErrorFeedback:
    """Per-leaf residual accumulator: g' = Q(g + e); e = (g + e) − g'."""

    def __init__(self, params_like):
        self.residual = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params_like
        )

    def apply(self, grads, reduce_fn):
        def one(g, e):
            x = g.astype(jnp.float32) + e
            q, scale = _quant(x)
            sent = _dequant(q, scale)
            new_e = x - sent
            return reduce_fn(sent), new_e

        pairs = jax.tree_util.tree_map(one, grads, self.residual)
        reduced = jax.tree_util.tree_map(
            lambda t: t[0], pairs, is_leaf=lambda t: isinstance(t, tuple)
        )
        self.residual = jax.tree_util.tree_map(
            lambda t: t[1], pairs, is_leaf=lambda t: isinstance(t, tuple)
        )
        return reduced
