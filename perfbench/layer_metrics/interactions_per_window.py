"""Device interactions (``gang.interactions``) a batched window."""


def read(ctx):
    return ctx["facts"].get("interactions_per_window")
