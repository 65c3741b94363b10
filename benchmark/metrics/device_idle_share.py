"""Share of the traced window in which no op runs on the device, as a
fraction, a mean over the chips (profiler trace)."""


def read(ctx):
    return ctx.trace.idle_share()
