"""``coll_device_busbw`` over the published per-chip ICI rate, %.  A 2x2
host wires only part of a chip's ICI ports, so 100% is not reachable
there; the share says how far the device programs are from the sheet."""

from perfbench.layer_metrics import coll_device_busbw


def read(ctx):
    bw = coll_device_busbw.read(ctx)
    if bw is None:
        return None
    return 100.0 * bw * 1e9 / ctx["peaks"]["ici_bytes_per_s"]
