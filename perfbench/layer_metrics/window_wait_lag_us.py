"""How late the drainer's ``block_until_ready`` returns: ``accl.ring::wait``'s
end minus the end of the window's last device op on any chip, on the
profiler's one clock; median over the windows, us a window.  Not
clamped."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.wait_lag)
