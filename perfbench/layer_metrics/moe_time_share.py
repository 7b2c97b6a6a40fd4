"""Device time under the ``accl.moe::`` scopes (route, dispatch, experts,
combine, forward and backward) over device busy time, traced steps, %."""

from perfbench.layer_metrics import _moe


def read(ctx):
    return _moe.share(ctx, None)
