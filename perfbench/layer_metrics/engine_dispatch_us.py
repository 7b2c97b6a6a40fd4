"""Median duration of ``accl.gang::dispatch`` (the one call of the
prepared program) in the small slice, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.duration_us(ctx, stage_spans.DISPATCH)
