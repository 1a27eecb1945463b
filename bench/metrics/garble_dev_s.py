"""Device seconds per bundle of the garbling walks. The program's
``garble`` span ends when the walk is dispatched, not when it is done, so
the time is read from the trace: the compiled garbling programs' device
time over the window's bundles."""

import re

#: the compiled garbling walk, as the trace names its program
MODULE = r"^jit__garble_fn\("


def read(ctx):
    if ctx.window.phase != "offline" or ctx.trace is None or not ctx.units:
        return None
    t = sum(v for k, v in ctx.trace["module_s"].items()
            if re.search(MODULE, k))
    if t <= 0:
        return None
    return t / ctx.units
