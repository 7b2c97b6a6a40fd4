"""Device time under ``accl.attn::ssd`` (the Mamba-2 core: the chunked
selective state-space recurrence from x, B, C and dt to y, forward,
``remat``'s second forward and backward, its scan over the chunks with it)
over device busy time, traced steps, %."""

from perfbench.layer_metrics import _nemotron3


def read(ctx):
    return _nemotron3.share(ctx, _nemotron3.CORE)
