"""Set-up: process start to the end of the warm-up job (imports, device
start, input generation, compiles or compile-cache loads, the warm-up)."""


def read(ctx):
    return ctx["setup_s"]
