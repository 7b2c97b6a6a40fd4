"""The slot words onto the chips: median duration of ``accl.ring::slots``
(``np.concatenate`` / ``np.tile`` / ``jax.device_put`` in ``run_windows``,
inside ``accl::cmdring[n]``) over the windows, us a window."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.slots_put)
