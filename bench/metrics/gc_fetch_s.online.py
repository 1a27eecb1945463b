"""Seconds per request the evaluator waits for its garbled-circuit
evaluations and copies their output labels off the device (``gc.fetch``
spans under ``online/``)."""

from bench import spans as S


def read(ctx):
    return S.seconds_per_unit(ctx, "online", ("gc.fetch",))
