"""Device seconds of one HEEV solve in the SBR band shrink: the self time
of every op of the program the library names ``jit_sbr_chunk``, a mean over
the chips, per traced solve (profiler trace).  Nothing to read where no op
carries that program's name."""

PROGRAM = "jit_sbr_chunk/"


def read(ctx):
    ns = sum(t for d in ctx.trace.devices.values() for label, t in d.op_ns.items()
             if label.startswith(PROGRAM))
    if not ns or not ctx.solves:
        return None
    return ns / ctx.trace.n / ctx.solves / 1e9
