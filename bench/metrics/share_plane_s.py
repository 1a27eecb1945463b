"""Seconds per request of share-plane products: both parties'
``share.matmul`` spans (object-dtype modular matmuls in ``op:linear`` and
``op:beaver_matmul``), each instant counted once."""

from bench import spans as S


def read(ctx):
    return S.seconds_per_unit(ctx, "online", ("share.matmul",))
