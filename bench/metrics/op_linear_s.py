"""Evaluator-side ``op:linear`` seconds per request (the share plane's
linear layers), from the program's spans in the traced window."""


def read(ctx):
    if ctx.window.phase != "online" or not ctx.units:
        return None
    s = [sp for sp in ctx.spans
         if sp.name == "op:linear" and sp._tid != ctx.client_tid]
    if not s:
        return None
    return sum(sp.elapsed_s for sp in s) / ctx.units
