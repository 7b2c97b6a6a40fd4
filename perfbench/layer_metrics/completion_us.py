"""Completion of a small gang call: end of the engine's ``accl::<op>``
span to the end of the drainer's ``accl.window::complete`` (the device
done-probe, the four requests completed); median over gang calls, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.per_call_us(ctx, stage_spans.completion)
