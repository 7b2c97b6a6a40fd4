"""Rank rendezvous of a small gang call: start of the engine's
``accl::<op>`` span (the last rank has arrived) minus the earliest
``accl.facade::call`` start of that call; median over gang calls, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.per_call_us(ctx, stage_spans.rendezvous)
