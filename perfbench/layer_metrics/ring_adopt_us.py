"""The window's results adopted by the ranks' receive buffers, then its
completion handed to the drainer: ``accl.ring::adopt`` +
``accl.ring::park``; median over the windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.per_window_us(ctx, window_spans.adopt_park)
