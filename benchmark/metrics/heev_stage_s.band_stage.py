"""Seconds of one HEEV solve in the library's ``band_stage`` (the SBR band
shrink on the device and the bulge chase on the host), from the stage
timer (``dlaf_tpu.common.stagetimer``) over a solve of its own."""


def read(ctx):
    return ctx.stage_s.get("band_stage")
