"""Whole-step share of the chip's bf16 peak: the plaintext forward pass's
FLOPs per request times requests per second over the window."""


def read(ctx):
    if ctx.window.phase != "online" or not ctx.units or not ctx.peaks:
        return None
    rate = ctx.flops_per_request * ctx.units / ctx.window.seconds
    return 100.0 * rate / ctx.peaks["bf16_flops_per_s"]
