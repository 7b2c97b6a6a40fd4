"""Device idle share inside the trace slice of the small blocking calls:
1 - (seconds in which an op ran on a device, averaged over the chips) /
(traced seconds), %."""

from perfbench.layer_metrics import _common


def read(ctx):
    return _common.idle_share([_common.slice_of(ctx, "small")])
