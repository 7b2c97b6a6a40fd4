"""Plan-cache hits over lookups, all rank handles, over the window, %."""


def read(ctx):
    lookups = ctx["facts"].get("plan_lookups")
    if not lookups:
        return None
    return 100.0 * ctx["facts"]["plan_hits"] / lookups
