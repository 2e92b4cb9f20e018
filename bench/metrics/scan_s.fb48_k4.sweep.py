"""Device seconds of the allocation scan per sweep in fb48_k4.sweep."""

PROGRAMS = ("jit__scan_all",)


def read(ctx):
    return ctx.device_per_unit(PROGRAMS)
