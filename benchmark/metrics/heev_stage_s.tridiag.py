"""Seconds of one HEEV solve in the library's ``tridiag`` stage (the host
LAPACK leaves and the device divide-and-conquer merges), from the stage
timer over a solve of its own."""


def read(ctx):
    return ctx.stage_s.get("tridiag")
