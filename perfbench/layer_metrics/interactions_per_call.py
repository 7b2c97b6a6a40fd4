"""Device interactions (``gang.interactions``) a blocking call, large and
small phases together; 1.0 is the single-interaction discipline."""


def read(ctx):
    return ctx["facts"].get("interactions_per_call")
