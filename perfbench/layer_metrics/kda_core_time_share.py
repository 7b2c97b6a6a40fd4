"""Device time under ``accl.attn::kda`` (the KDA core: the chunked gated
delta rule from normalised q, k, v, the log-decay and beta to o, forward,
``remat``'s second forward and backward, its scan over the chunks with it)
over device busy time, traced steps, %."""

from perfbench.layer_metrics import _ling3


def read(ctx):
    return _ling3.share(ctx, _ling3.CORE)
