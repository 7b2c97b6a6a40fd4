"""FLOP/s utilization of the SDAR step ON THIS CHIP: FLOPs its model does
a step from shapes and from the counted held entries (``flops_sdar.py``:
6 x the matmul parameters a row of ``[noisy ; clean]`` passes here, the
head by the noisy half's rows, the routed experts by the entries held, the
attention core by its live pairs; the flash backward's rebuilt scores NOT
counted) a DATA token, times this run's tokens/s over the chip's bf16
peak, %: the share of the whole step."""

from perfbench import flops_sdar
from perfbench.layer_metrics import _afmoe


def read(ctx):
    f = ctx["facts"]
    rate = f.get("tokens_per_s")
    held = _afmoe.held_entries_a_step(ctx)
    if not rate or held is None or "diffusion" not in f:
        return None
    per_step = flops_sdar.train_flops_per_step(
        ctx["cell"]["config"], f["seq"], f["batch"], held
    )
    return (
        100.0 * per_step / f["tokens_per_step"] * rate
        / ctx["peaks"]["bf16_flops_per_s"]
    )
