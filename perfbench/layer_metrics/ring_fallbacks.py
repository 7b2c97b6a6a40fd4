"""Windows' slots that left the command ring during the window (the sum
of the ring's fallback table, after minus before)."""


def read(ctx):
    return ctx["facts"].get("ring_fallbacks")
