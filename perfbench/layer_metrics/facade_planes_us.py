"""What the service planes cost a warm small call on a rank thread:
``accl.facade::membership`` + ``arbiter`` + ``contract`` + ``meta``, summed
a rank call; median over the rank calls of the small slice, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.per_rank_call_us(ctx, stage_spans.planes)
