"""The benchmark's own share of a small gang call: latest minus earliest
``bench::small::<op>`` start of the call's rank threads (each opens its
span as the gate releases it); median over the gang calls, us."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_call_us(ctx, runtime_spans.gate_spread)
