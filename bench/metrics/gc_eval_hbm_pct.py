"""Evaluation kernel's share of the HBM roofline: the bytes evaluating
every garbled circuit of the window's requests has to move (32 B of table
per AND gate, 16 B per input and output label, counted from the program's
netlists and instances by ``bench.counts``) over the evaluation kernel's
device time, over the chip's HBM bandwidth."""

from bench import counts
from bench import trace as T

#: the fused level-evaluation Pallas kernel: the compiled walk names its
#: custom call after the jitted wrapper, ``eval_level_pallas``
KERNEL = r"^%eval_level_pallas\b"


def read(ctx):
    if ctx.window.phase != "online" or ctx.trace is None or not ctx.units:
        return None
    t = T.op_seconds(ctx.trace, KERNEL)
    if t <= 0:
        return None
    per_request = sum(counts.gc_bytes(w["and"], w["inputs"], w["outputs"],
                                      w["instances"]) for w in ctx.gc_work)
    return (100.0 * per_request * ctx.units / t
            / ctx.peaks["hbm_bytes_per_s"])
