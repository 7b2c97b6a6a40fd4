"""Wake of a small gang call: end of ``accl.window::complete`` to the
latest end of the call's ``bench::small::<op>`` spans (the rank threads
wake, return and find their outputs ready); median over gang calls, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.per_call_us(ctx, stage_spans.wake)
