"""Seconds and counters of the program's own spans in a traced window,
for the readers in ``bench/metrics/``.

A span is a ``repro.obs`` span: ``name``, ``path`` (its parents' names
joined by ``/``, the phase's root first), ``t0_ns``/``t1_ns`` on the host
clock, ``attrs``. Both parties' spans are in the list, each party on its
own threads.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence


def in_phase(ctx, phase: str, names: Sequence[str]) -> List:
    """The finished spans named one of ``names`` under ``phase``'s root
    span (``online/...`` or ``offline/...``)."""
    prefix = phase + "/"
    return [sp for sp in ctx.spans
            if sp.name in names and sp.t1_ns is not None
            and sp.path.startswith(prefix)]


def covered_s(spans: Iterable) -> float:
    """Seconds covered by the spans' intervals, each instant once however
    many spans hold it: a span nested in another, or both parties'
    spans at the same time."""
    total = 0
    end = None
    for t0, t1 in sorted((sp.t0_ns, sp.t1_ns) for sp in spans):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total * 1e-9


def seconds_per_unit(ctx, phase: str, names: Sequence[str]
                     ) -> Optional[float]:
    """Seconds per request (online) or bundle (offline) covered by the
    named spans of the window, or None where the window is of the other
    phase, did no unit of work, or holds no such span."""
    if ctx.window.phase != phase or not ctx.units:
        return None
    spans = in_phase(ctx, phase, names)
    if not spans:
        return None
    return covered_s(spans) / ctx.units


def attr_per_unit(ctx, phase: str, name: str, attr: str
                  ) -> Optional[float]:
    """The named spans' ``attr`` summed over the window, per request or
    bundle; None as in :func:`seconds_per_unit`."""
    if ctx.window.phase != phase or not ctx.units:
        return None
    spans = in_phase(ctx, phase, (name,))
    if not spans:
        return None
    return sum(sp.attrs.get(attr, 0) for sp in spans) / ctx.units
