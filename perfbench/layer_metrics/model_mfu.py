"""Model FLOP/s utilization: FLOPs a token from shapes (6 x matmul
parameters plus causal attention, no recomputation) times this run's
tokens/s over the chip's bf16 peak, %."""

from perfbench import flops


def read(ctx):
    rate = ctx["facts"].get("tokens_per_s")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(
        ctx["cell"]["config"], ctx["facts"]["seq"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
