"""Device milliseconds of the allocation scan per traced epoch."""

PROGRAMS = ("jit__scan_all",)


def read(ctx):
    return ctx.device_per_unit(PROGRAMS, scale=1e3)
