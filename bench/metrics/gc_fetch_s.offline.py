"""Seconds per bundle the garbler waits for its garbling walks and copies
the garbled tables off the device (``gc.fetch`` spans under
``offline/``)."""

from bench import spans as S


def read(ctx):
    return S.seconds_per_unit(ctx, "offline", ("gc.fetch",))
