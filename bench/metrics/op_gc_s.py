"""Evaluator-side seconds per request of the garbled-circuit ops
(``op:gc_apply``, ``op:trunc``, ``op:layernorm``), from the program's
spans in the traced window."""

OPS = ("op:gc_apply", "op:trunc", "op:layernorm")


def read(ctx):
    if ctx.window.phase != "online" or not ctx.units:
        return None
    s = [sp for sp in ctx.spans
         if sp.name in OPS and sp._tid != ctx.client_tid]
    if not s:
        return None
    return sum(sp.elapsed_s for sp in s) / ctx.units
