"""Device time under ``accl.moe::route`` + ``dispatch`` + ``combine``
(router matmul, softmax, top-k, sort, gathers, weighting: the part no MXU
helps) over device busy time, traced steps, %."""

from perfbench.layer_metrics import _moe


def read(ctx):
    return _moe.share(ctx, ("route", "dispatch", "combine"))
