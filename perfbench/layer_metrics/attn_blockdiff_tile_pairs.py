"""(q tile, k tile) pairs the flash kernels visit for one head of one
sequence under the block-diffusion layout, from the shapes and by the
kernels' own ranges (``accl_tpu.ops.pallas.attention.flash_tile_classes``
with ``block_diffusion``; the driver asks the program): 80 at L = 4096,
blocks of 4, tiles of 512, where the causal kernels at T = 8192 visit 136.

Not printed in a rehearsal, though it is a count (as
``moe_load_imbalance.py``)."""


def read(ctx):
    tiles = ctx["facts"].get("attention_tiles")
    if ctx["peaks"] is None or not tiles:
        return None
    return float(sum(tiles.values()))
