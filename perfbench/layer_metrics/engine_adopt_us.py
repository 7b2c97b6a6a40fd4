"""Median duration of ``accl.gang::adopt`` (output shards into the
result buffers) in the small slice, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.duration_us(ctx, stage_spans.ADOPT)
