"""Device time of the flash kernels under ``accl.attn::window`` (the
sliding layers' attention, forward and backward) over device busy time,
traced steps, %."""

from perfbench.layer_metrics import _afmoe


def read(ctx):
    found = _afmoe.flash_ns(ctx, _afmoe.WINDOW)
    return None if found is None else 100.0 * found[0] / found[1]
