"""Device time under ``accl.loss::diffusion`` (the head and the weighted
loss of the noisy half, forward and backward) and
``accl.diffusion::noise`` (the noising of the ids inside the step) over
device busy time, traced steps, %."""

from perfbench.layer_metrics import _sdar


def read(ctx):
    return _sdar.scope_share(ctx, (_sdar.LOSS, _sdar.NOISE))
