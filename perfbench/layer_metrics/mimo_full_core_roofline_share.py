"""``_mimo.core_roofline_share`` of the full layers: the least time for the
causal pairs ``T (T + 1) / 2`` a head over the device time of the flash
kernels under ``accl.attn::core`` (64 heads of 128 + 64 | 128 on 4 KV
heads, no sink), %."""

from perfbench.layer_metrics import _mimo


def read(ctx):
    return _mimo.core_roofline_share(ctx, False)
