"""Milliseconds per epoch: the window's elapsed host-clock time, taken
from outside `stream()`, over the epochs decided in it — so it includes
settling, admission and slot bookkeeping besides each decision."""


def read(ctx):
    rate = ctx.rate()
    return None if rate is None else 1e3 / rate
