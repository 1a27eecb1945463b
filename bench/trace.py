"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

A traced run wraps its measured window in the harness's own
``jax.profiler.TraceAnnotation`` named :data:`WINDOW`, and notes the host
clock (``time.perf_counter_ns``) as it enters it. That one pair puts the
program's own spans, which carry host-clock times, on the trace's
timeline, so every idle gap on the device can be named by what the host
was doing in it.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import xplane as X

WINDOW = "bench.window"
#: the profiler line that holds one event per device operation
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: ``%name = type[dims]`` at the head of an HLO instruction
_HLO = re.compile(r"%(\S+) = (\w+\[[^\]]*\])?")
#: spans that mean "waiting for the peer", not work
WAIT_SPANS = ("wire.recv",)


@contextlib.contextmanager
def recording(log_dir: str):
    """Profile the block into ``log_dir`` with Python call tracing off."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An HLO instruction's name and result shape, without layout:
    ``%fusion.27 = u32[8256,8,4]{...} fusion(...)`` -> ``fusion.27
    u32[8256,8,4]``. Instruction names repeat across programs, so the
    shape is kept to tell them apart."""
    m = _HLO.match(name)
    if not m:
        return name
    return f"{m.group(1)} {m.group(2)}" if m.group(2) else m.group(1)


def _leaves(events: List[Tuple[str, float, float]]):
    """The events that contain no other event of their line: a loop's
    body operations, not the loop that holds them."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, d) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= s:
            stack.pop()
        if stack and order[stack[-1]][1] + order[stack[-1]][2] >= s + d:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


def load(path: str) -> dict:
    """Device operations, device modules and host annotations of a trace.

    Returns ``{"devices": {plane: {"all_ops": [(name, start_ns, dur_ns)],
    "ops": [...], "modules": [...]}}, "annotations": [...],
    "names": {short op name: full op name}}``. Ops are named by their
    short names; ``ops`` keeps only the innermost ones (see
    :func:`_leaves`).
    """
    space = X.read_space(path)
    devices: Dict[str, dict] = {}
    annotations: List[Tuple[str, float, float]] = []
    names: Dict[str, str] = {}
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            short = {}
            dev = {"all_ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for mid, s, d in X.line_events(line):
                        if mid not in short:
                            full = meta[mid].name
                            short[mid] = short_name(full)
                            names.setdefault(short[mid], full)
                        dev["all_ops"].append((short[mid], s, d))
                elif line.name == MODULES_LINE:
                    dev["modules"].extend((meta[mid].name, s, d) for mid, s, d
                                          in X.line_events(line))
            if dev["all_ops"]:
                dev["ops"] = _leaves(dev["all_ops"])
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            ours = {mid for mid, m in meta.items()
                    if m.name.startswith("bench.")}
            for line in plane.lines:
                annotations.extend((meta[mid].name, s, d) for mid, s, d
                                   in X.line_events(line) if mid in ours)
    return {"devices": devices, "annotations": annotations, "names": names}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events, lo: float, hi: float):
    for name, s, d in events:
        e = s + d
        if e > lo and s < hi:
            yield name, max(s, lo), min(e, hi)


def window_of(trace: dict) -> Tuple[float, float]:
    wins = [(s, s + d) for n, s, d in trace["annotations"] if n == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    return wins[0]


class HostSpans:
    """The program's spans, moved onto the trace's clock."""

    def __init__(self, spans: Sequence, offset_ns: float, client_tid: int):
        rows = []
        for sp in spans:
            if sp.t1_ns is None or sp.name in WAIT_SPANS:
                continue
            side = "client" if sp._tid == client_tid else "server"
            rows.append((sp.t0_ns - offset_ns, sp.t1_ns - offset_ns,
                         sp.path.count("/"), f"{side} {sp.path}"))
        rows.sort()
        self.rows = rows

    def label_points(self, points: Sequence[float]) -> List[Optional[str]]:
        """The innermost span that covers each (ascending) point."""
        out: List[Optional[str]] = []
        active: List[tuple] = []
        i = 0
        for p in points:
            while i < len(self.rows) and self.rows[i][0] <= p:
                active.append(self.rows[i])
                i += 1
            active = [r for r in active if r[1] >= p]
            best = max(active, key=lambda r: r[2], default=None)
            out.append(best[3] if best else None)
        return out


def reduce(trace: dict, *, host: Optional[HostSpans] = None,
           n_top: int = 10) -> dict:
    """Busy and idle time of the devices inside the window, the device
    time of every operation and module by name, and the longest idle
    stretches summed by what the host was doing in them."""
    w0, w1 = window_of(trace)
    window_s = (w1 - w0) * 1e-9
    if not trace["devices"]:
        raise ValueError("trace has no device operations")
    busy = []
    op_s: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in sorted(trace["devices"]):
        dev = trace["devices"][plane]
        for name, s, e in _clip(dev["ops"], w0, w1):
            op_s[name] += (e - s) * 1e-9
        ivs = [(s, e) for _, s, e in _clip(dev["all_ops"], w0, w1)]
        for name, s, e in _clip(dev["modules"], w0, w1):
            module_s[name] += (e - s) * 1e-9
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if not gaps:  # idle stretches of the first device
            edge = w0
            for s, e in merged:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, e)
            if w1 > edge:
                gaps.append((edge, w1))
    n = len(busy)
    idle_by: Dict[str, float] = defaultdict(float)
    if gaps:
        mids = [(s + e) / 2 for s, e in gaps]
        labels = (host.label_points(mids) if host is not None
                  else [None] * len(gaps))
        ann = _annotation_labels(trace, mids)
        for (s, e), lab, a in zip(gaps, labels, ann):
            idle_by[lab or a or "host outside any span"] += (e - s) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "devices": n,
        "names": trace.get("names", {}),
        "op_s": dict(op_s),
        "module_s": dict(module_s),
        "device_ops": top(op_s, n_top),
        "idle_gaps": top(idle_by, n_top),
    }


def _annotation_labels(trace: dict, points: Sequence[float]
                       ) -> List[Optional[str]]:
    anns = sorted((s, s + d, n) for n, s, d in trace["annotations"]
                  if n != WINDOW)
    out = []
    for p in points:
        inner = [n for s, e, n in anns if s <= p <= e]
        out.append(inner[-1] if inner else None)
    return out


def top(d: Dict[str, float], n: int) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def op_seconds(reduced: dict, pattern: str) -> float:
    """Summed device seconds of the innermost operations whose full name
    (the HLO instruction) ``pattern``, a regex, matches."""
    rx = re.compile(pattern)
    names = reduced["names"]
    return sum(v for k, v in reduced["op_s"].items()
               if rx.search(names.get(k, k)))
