"""Milliseconds of the ordering LP per epoch: the mean of
`EpochRecord.lp_wall_s` (the program's host-clock span around each
epoch's warm-started solve) over the traced stream's epochs."""


def read(ctx):
    walls = [e.lp_wall_s for o in ctx.outs for e in o.epochs]
    return 1e3 * sum(walls) / len(walls)
