"""Megabytes per request the evaluator copies to the device before its
garbled-circuit evaluations: the ``bytes`` of its ``gc.upload`` spans
(each op's band of the bundle's tables plus its labels)."""

from bench import spans as S


def read(ctx):
    b = S.attr_per_unit(ctx, "online", "gc.upload", "bytes")
    return None if b is None else b / 1e6
