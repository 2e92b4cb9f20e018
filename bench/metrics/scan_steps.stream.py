"""Allocation-scan steps per epoch: the program's `alloc.steps` counter
(the flow steps each allocation loop ran, counted from host values;
`EpochRecord.counts`) over the traced stream's epochs.  None where the
program does not count them."""


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not any("alloc.steps" in getattr(e, "counts", {})
                             for e in epochs):
        return None
    return sum(e.counts.get("alloc.steps", 0) for e in epochs) / len(epochs)
