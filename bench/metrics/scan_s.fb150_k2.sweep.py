"""Device seconds of the allocation scan per sweep in fb150_k2.sweep."""

PROGRAMS = ("jit__scan_all",)


def read(ctx):
    return ctx.device_per_unit(PROGRAMS)
