"""Flows scheduled per second in fb48_k4.sweep: every flow of every `sweep()`
call in the window over the window's elapsed host-clock seconds (each
call returns host arrays, so its device work has finished)."""


def read(ctx):
    return ctx.rate()
