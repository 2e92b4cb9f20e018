"""Seconds of the ordering LP per sweep in fb48_k4.sweep:
`SweepResult.lp_time_s` (the program's host-clock span around the LP
phase, which ends in host arrays), averaged over the traced sweeps."""


def read(ctx):
    return sum(o.lp_time_s for o in ctx.outs) / len(ctx.outs)
