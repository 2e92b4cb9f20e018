"""Percent of the traced stream in which no operation ran on the device:
it covers the service's host bookkeeping (settling, admission, slot
scatter, packing) between device programs."""


def read(ctx):
    return ctx.idle_percent()
