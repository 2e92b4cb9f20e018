"""Host milliseconds of the circuit calendar per epoch: the program's
`calendar.pack` (member tables, padding, pair sort, placement),
`calendar.unpack` (bit patterns back, unsort, stall checks) and
`calendar.readback` (schedules and CCTs) spans (`EpochRecord.spans`) over
the traced stream's epochs; the wait for the device program is not in
it.  None where the program records no spans."""

SPANS = ("calendar.pack", "calendar.unpack", "calendar.readback")


def read(ctx):
    epochs = [e for o in ctx.outs for e in o.epochs]
    if not epochs or not hasattr(epochs[0], "spans"):
        return None
    return 1e3 * sum(e.spans.get(n, 0.0) for e in epochs
                     for n in SPANS) / len(epochs)
