"""Tokens whose kept routing groups include the group this chip holds
over all tokens, every expert layer, %: counted by the program's router
probe (``group_tokens``) on the first batch after set-up's balance
rounds.  Balanced it is ``topk_group / n_group`` (37.5%): the share of
the tokens that would be sent to this chip at all.

Not printed in a rehearsal, though it is a count (as
``moe_load_imbalance.py``)."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    return (ctx["facts"].get("router") or {}).get("group_hit_share")
