"""Percent of the traced sweep in fb150_k2.sweep in which no operation ran on
the device."""


def read(ctx):
    return ctx.idle_percent()
