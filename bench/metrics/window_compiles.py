"""Compilations inside the measured window; a steady window has none."""


def read(ctx):
    return ctx["window_compiles"]
