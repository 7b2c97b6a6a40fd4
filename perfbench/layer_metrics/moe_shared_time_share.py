"""Device time under ``accl.moe::shared`` (the shared expert's three
matmuls, forward and backward) over device busy time, traced steps, %."""

from perfbench.layer_metrics import _moe


def read(ctx):
    return _moe.share(ctx, ("shared",))
