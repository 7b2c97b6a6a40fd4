"""Device time under ``accl.attn::kda_proj`` (the KDA mixer round its core:
the seven projections, the three convolutions, SiLU, the L2 norms, the gate
and beta, the output norm and gate, ``wo``; forward, ``remat``'s second
forward and backward) over device busy time, traced steps, %."""

from perfbench.layer_metrics import _ling3


def read(ctx):
    return _ling3.share(ctx, _ling3.PROJ)
