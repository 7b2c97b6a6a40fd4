"""How late the host learns that the device is done: end of
``accl.window::ready`` (the drainer's ``block_until_ready``) minus the end
of the call's last device op on any chip, host span and device op on the
profiler's one clock; median over the gang calls of the small slice, us.
Not clamped: a negative reading says the clocks are not shared."""

from perfbench import stage_spans


def read(ctx):
    return stage_spans.per_call_us(ctx, stage_spans.ready_lag)
