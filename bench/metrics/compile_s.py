"""Seconds spent compiling (or loading from the persistent cache) before
the window: JAX's ``backend_compile_duration`` events, summed."""


def read(ctx):
    return ctx["setup_compile_s"]
