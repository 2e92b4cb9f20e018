"""Lockstep calendar rounds per epoch: the program's `calendar.rounds`
counter (the loop count of each calendar program, read back from the
device; `EpochRecord.counts`) over the traced stream's epochs.  None
where the program does not count them."""


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not hasattr(epochs[0], "counts"):
        return None
    return sum(e.counts.get("calendar.rounds", 0)
               for e in epochs) / len(epochs)
