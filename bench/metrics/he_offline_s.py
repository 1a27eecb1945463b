"""Seconds per bundle of the offline HE and share-plane work: both
parties' ``he.offline`` spans (HE masks, ``r1 W``, Beaver dealing, the
LayerNorm HE segments), each instant counted once."""

from bench import spans as S


def read(ctx):
    return S.seconds_per_unit(ctx, "offline", ("he.offline",))
