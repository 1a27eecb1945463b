"""Set-up seconds: process start to the first timed request or bundle."""


def read(ctx):
    return ctx.setup_s
