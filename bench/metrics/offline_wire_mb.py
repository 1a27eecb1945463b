"""Megabytes per bundle on the client's offline socket, both directions,
sideband included, over the window: the kernel's TCP byte counts, read by
the harness."""


def read(ctx):
    wire = ctx.window.wire.get("offline")
    if wire is None or not ctx.units:
        return None
    return wire["bytes"] / ctx.units / 1e6
