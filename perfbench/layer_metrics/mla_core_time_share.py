"""Device time of the flash kernels under ``accl.attn::mla`` (the latent
attention's core, forward, ``remat``'s second forward and backward) over
device busy time, traced steps, %."""

from perfbench.layer_metrics import _afmoe, _dsv2


def read(ctx):
    found = _afmoe.flash_ns(ctx, _dsv2.CORE)
    return None if found is None else 100.0 * found[0] / found[1]
