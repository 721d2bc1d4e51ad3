"""Seconds set-up spent compiling or loading compiled programs, from
JAX's compile-duration events, read when the window opens: the program's
compiles, not the plain reference's that follow the window."""


def read(ctx):
    return ctx["compile_s"]
