"""1 - (seconds in which an op ran on the device) / (traced seconds), over
every slice of the run, %."""

from perfbench.layer_metrics import _common


def read(ctx):
    return _common.idle_share(ctx.get("slices", {}).values())
