"""Share of the device's busy time in which a collective runs and no other
op does, as a fraction, a mean over the chips (profiler trace).  Nothing
to read where no collective ran."""


def read(ctx):
    if not ctx.trace.collective_ns():
        return None
    return ctx.trace.collective_exposed_share()
