"""Dispatch wrapper for the fused level evaluator / garbler.

``impl`` resolution goes through :func:`repro.kernels.dispatch.resolve_impl`
so ``auto`` means the same thing here as in every other kernel wrapper and
in ``core.garble``. ``"ref"`` and ``"jit"`` both select the jnp oracle —
that *is* the jit-able path; the distinction only matters one level up.
"""

from __future__ import annotations

from repro.kernels.dispatch import resolve_impl
from repro.kernels.level_eval import ref as _ref
from repro.kernels.level_eval.level_eval import (
    eval_level_pallas,
    garble_level_pallas,
)


def eval_level(ops, a, b, tg, te, tweaks, impl: str = "auto"):
    impl = resolve_impl(impl)
    if impl in ("ref", "jit"):
        return _ref.eval_level(ops, a, b, tg, te, tweaks)
    return eval_level_pallas(ops, a.T, b.T, tg.T, te.T, tweaks,
                             interpret=(impl == "pallas_interpret")).T


def garble_level(ops, a0, b0, r, tweaks, impl: str = "auto"):
    impl = resolve_impl(impl)
    if impl in ("ref", "jit"):
        return _ref.garble_level(ops, a0, b0, r, tweaks)
    c0, tg, te = garble_level_pallas(ops, a0.T, b0.T, r.T, tweaks,
                                     interpret=(impl == "pallas_interpret"))
    return c0.T, tg.T, te.T
