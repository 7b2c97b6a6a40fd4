"""How late the host learns that a batched window is done: end of
``accl.window::ready`` (the drainer's ``block_until_ready`` on the status
words, and their read-back) minus the end of the window's last device op
on any chip, on the profiler's one clock; median over the windows, us a
window.  Not clamped: a negative reading says the clocks are not shared."""

from perfbench import window_spans


def read(ctx):
    return window_spans.per_window_us(ctx, window_spans.ready_lag)
