"""Host seconds of one HEEV solve in the band back-transform's host
preparation (the group schedule, the compact-WY factors and their copies
to the device): the library's ``bt_band/factors`` spans inside the traced
window, per traced solve (profiler trace, host clock of the trace).
Nothing to read where the library opens no such span."""

SPAN = "bt_band/factors"
WINDOW = "bench/traced_window"


def read(ctx):
    host = ctx.trace.host
    spans = [(s, e) for name, s, e in host if name == SPAN]
    window = [(s, e) for name, s, e in host if name == WINDOW]
    if not spans or not window or not ctx.solves:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    ns = sum(min(e, hi) - max(s, lo) for s, e in spans if min(e, hi) > max(s, lo))
    return ns / ctx.solves / 1e9
