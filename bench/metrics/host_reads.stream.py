"""Device-to-host reads per epoch: the program's `host_reads` counter
(every device array its stage and service code turns into a host value;
`EpochRecord.counts`) over the traced stream's epochs.  None where the
program does not count them."""


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not hasattr(epochs[0], "counts"):
        return None
    return sum(e.counts.get("host_reads", 0) for e in epochs) / len(epochs)
