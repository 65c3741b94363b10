"""Programs compiled or loaded from the persistent cache per traced solve:
the library's ``program_load/<phase>/<program>`` spans (one per compile or
load, over it) that start inside the traced window, per traced solve
(profiler trace).  A library that does not mark its loads
(``dlaf_tpu.obs.program_loads`` absent) gives nothing to read; one that
marks them and loads nothing reads 0."""

PREFIX = "program_load/"
WINDOW = "bench/traced_window"


def read(ctx):
    import dlaf_tpu.obs

    window = [(s, e) for name, s, e in ctx.trace.host if name == WINDOW]
    if not hasattr(dlaf_tpu.obs, "program_loads") or not window or not ctx.solves:
        return None
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    loads = sum(1 for name, s, _ in ctx.trace.host if name.startswith(PREFIX) and lo <= s < hi)
    return loads / ctx.solves
