"""Device time under ``accl.moe::latent`` (a LatentMoE's two projections:
the token into the experts' width before the dispatch, the tokens' weighted
sums out of it after the combine; forward, ``remat``'s second forward and
backward) over device busy time, traced steps, %.  ``moe_time_share``, which
sums every ``accl.moe::`` scope of the entry computation, holds this time
too."""

from perfbench.layer_metrics import _nemotron3


def read(ctx):
    return _nemotron3.share(ctx, _nemotron3.LATENT)
