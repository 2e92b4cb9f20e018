"""85th percentile of `EpochRecord.wall_s` over every epoch of every
stream in the window, in milliseconds (linear interpolation).  A 51-s
window holds four streams of 22 epochs: 13 epochs lie beyond p85."""

import numpy as np


def read(ctx):
    walls = [e.wall_s for o in ctx.outs for e in o.epochs]
    return float(np.percentile(walls, 85)) * 1e3
