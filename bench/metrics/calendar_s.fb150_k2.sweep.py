"""Device seconds of the kernel-engine circuit calendar per sweep in
fb150_k2.sweep (its plain and buffer-donating builds share one name)."""

PROGRAMS = ("jit__run_calendar_pairs_impl",)


def read(ctx):
    return ctx.device_per_unit(PROGRAMS)
