"""Device time of the flash kernels under ``accl.attn::core`` (the one
softmax layer's causal attention, 64 query heads on 8 KV heads: forward,
``remat``'s second forward and backward) over device busy time, traced
steps, %."""

from perfbench.layer_metrics import _afmoe, _solar2


def read(ctx):
    if not _solar2.layers(ctx, "gqa_layers"):
        return None
    found = _afmoe.flash_ns(ctx, _solar2.GQA_CORE)
    return None if found is None else 100.0 * found[0] / found[1]
