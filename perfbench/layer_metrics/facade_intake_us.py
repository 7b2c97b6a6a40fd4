"""Intake of a small blocking call on a rank's calling thread: start of
``accl.facade::call`` to the start of its ``accl.facade::submit`` (plan
lookup, option building, the service planes); median over every rank
thread's call of the small slice, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.per_rank_call_us(ctx, stage_spans.intake)
