"""Model FLOP/s utilization of the OLMoE step: FLOPs a token from shapes
(``flops_olmoe.py``: 6 x ACTIVE matmul parameters, the eight chosen
experts of 64, plus causal attention, no recomputation) times this run's
tokens/s over the chip's bf16 peak, %."""

from perfbench import flops_olmoe


def read(ctx):
    rate = ctx["facts"].get("tokens_per_s")
    if not rate:
        return None
    per_token = flops_olmoe.train_flops_per_token(
        ctx["cell"]["config"], ctx["facts"]["seq"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
