"""Of a gang call's dispatch, the client's own execute: the runtime's ONE
execute event (``runtime_spans.EXECUTE``) inside ``accl.gang::dispatch``
on its thread; median over the gang calls, us.  None where the trace
holds no such event."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_call_us(ctx, runtime_spans.call_execute)
