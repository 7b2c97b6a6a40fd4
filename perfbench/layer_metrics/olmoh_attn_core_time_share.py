"""Device time of the flash kernels under ``accl.attn::core`` (the one
full-attention layer's causal attention, 30 heads of 128 on 30 KV heads:
forward, ``remat``'s second forward and backward) over device busy time,
traced steps, %."""

from perfbench.layer_metrics import _afmoe, _olmoh


def read(ctx):
    if not _olmoh.layers(ctx, "full_layers"):
        return None
    found = _afmoe.flash_ns(ctx, _olmoh.ATTN_CORE)
    return None if found is None else 100.0 * found[0] / found[1]
