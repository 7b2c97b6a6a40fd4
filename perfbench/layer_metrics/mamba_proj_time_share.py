"""Device time under ``accl.attn::mamba_proj`` (the Mamba-2 mixer round its
core: the five input projections, the convolutions with their bias, SiLU,
softplus, the gate, the grouped norm, ``wo``; forward, ``remat``'s second
forward and backward) over device busy time, traced steps, %."""

from perfbench.layer_metrics import _nemotron3


def read(ctx):
    return _nemotron3.share(ctx, _nemotron3.PROJ)
