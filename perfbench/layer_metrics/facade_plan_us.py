"""Median duration of ``accl.facade::plan`` (the plan-cache lookup,
``ACCL._plan_for``) over the rank calls of the small slice, us."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.duration_us(ctx, stage_spans.PLAN)
