"""Seconds per request of oblivious transfer on the host: both parties'
``ot.base``, ``ot.extend``, ``ot.receive`` and ``ot.respond`` spans (the
IKNP extension, its answer and the one-time base OT), each instant
counted once."""

from bench import spans as S

NAMES = ("ot.base", "ot.extend", "ot.receive", "ot.respond")


def read(ctx):
    return S.seconds_per_unit(ctx, "online", NAMES)
