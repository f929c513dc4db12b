"""setup_s: process start to the first timed request (host clock):
weights, compile to logic, synthesis, kernel compile or cache load,
warm-up traffic."""


def read(ctx):
    return ctx.setup_s
