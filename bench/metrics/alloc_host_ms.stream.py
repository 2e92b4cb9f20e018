"""Host milliseconds of the allocation scan per epoch: the program's
`alloc.prepare` (permute, gather, bit patterns, placement) and
`alloc.unpack` (bit patterns back, prefix bounds) spans
(`EpochRecord.spans`) over the traced stream's epochs; the wait for the
device program is not in it.  None where the program records no spans."""

SPANS = ("alloc.prepare", "alloc.unpack")


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not hasattr(epochs[0], "spans"):
        return None
    return 1e3 * sum(e.spans.get(n, 0.0) for e in epochs
                     for n in SPANS) / len(epochs)
