"""Device time of the flash kernels under ``accl.attn::blockdiff`` (the
attention core under the block-diffusion layout, forward and backward)
over device busy time, traced steps, %."""

from perfbench.layer_metrics import _afmoe, _sdar


def read(ctx):
    found = _afmoe.flash_ns(ctx, _sdar.CORE)
    return None if found is None else 100.0 * found[0] / found[1]
