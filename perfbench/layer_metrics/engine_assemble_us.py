"""Median duration of ``accl.gang::assemble`` (operand checks, the
assembled global, the program lookup) in the small slice, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.duration_us(ctx, stage_spans.ASSEMBLE)
