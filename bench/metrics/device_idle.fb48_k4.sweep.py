"""Percent of the traced sweep in fb48_k4.sweep in which no operation ran on
the device."""


def read(ctx):
    return ctx.idle_percent()
