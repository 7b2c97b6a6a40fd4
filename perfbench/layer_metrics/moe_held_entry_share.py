"""Routing entries whose expert this chip holds over all of the batch's
entries (tokens x experts per token), every expert layer, %: counted by
the program's router probe on the first batch at the seeded weights.
The balanced share is ``num_experts / num_router_experts`` (12.5%).

Not printed in a rehearsal, though it is a count: ``tests/
test_rehearsal.py`` lists the counts a rehearsal may print, and that
file is not this PR's to edit (as ``moe_load_imbalance.py``)."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    return (ctx["facts"].get("router") or {}).get("held_entry_share")
