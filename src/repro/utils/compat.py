"""Version compatibility shims for the jax API surface we rely on."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by
    default (the collectives here are written per shard)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
