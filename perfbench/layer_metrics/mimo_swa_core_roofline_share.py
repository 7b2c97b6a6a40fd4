"""``_mimo.core_roofline_share`` of the sliding layers: the least time for
the IN-WINDOW pairs ``T W - W (W - 1) / 2`` a head (W = 128) over the device
time of the flash kernels under ``accl.attn::window``, %.  The kernels visit
31 tiles of 512 x 512 a head at T = 8,192 for 1,040,448 pairs in the window:
about an eighth of what they multiply is useful, and this share says so."""

from perfbench.layer_metrics import _mimo


def read(ctx):
    return _mimo.core_roofline_share(ctx, True)
