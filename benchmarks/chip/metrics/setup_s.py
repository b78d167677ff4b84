"""Set-up: process start to the start of the measured window (imports,
device, inputs, the program's setup and first call, all compilation)."""


def read(ctx):
    return ctx.setup_s
