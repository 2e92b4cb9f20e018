"""Device milliseconds of the kernel-engine circuit calendar per traced
epoch."""

PROGRAMS = ("jit__run_calendar_pairs_impl",)


def read(ctx):
    return ctx.device_per_unit(PROGRAMS, scale=1e3)
