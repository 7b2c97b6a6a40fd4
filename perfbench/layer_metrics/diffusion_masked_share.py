"""Masked positions over the noisy half's positions, every step the run
made, %: the program's own ``masked_tokens`` counter, which each train
step returns.  A level a block uniform on [0.001, 1) gives about 50.

Not printed in a rehearsal, though it is a count (as
``moe_load_imbalance.py``)."""


def read(ctx):
    d = ctx["facts"].get("diffusion")
    if ctx["peaks"] is None or not d or not d.get("noisy_positions"):
        return None
    return 100.0 * d["masked_tokens"] / d["noisy_positions"]
