"""Seconds per request the evaluator spends copying each garbled-circuit
op's tables and labels to the device (``gc.upload`` spans)."""

from bench import spans as S


def read(ctx):
    return S.seconds_per_unit(ctx, "online", ("gc.upload",))
