"""Seconds per bundle of the garbled-table stream's codec on the host:
the garbler's ``tables.pack`` (delta packing and the seed-stream record),
the evaluator's ``tables.unpack`` and ``labels.stream`` (the mask-label
stream replayed from its seed), each instant counted once."""

from bench import spans as S

NAMES = ("tables.pack", "tables.unpack", "labels.stream")


def read(ctx):
    return S.seconds_per_unit(ctx, "offline", NAMES)
