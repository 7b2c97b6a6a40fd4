"""The largest tokens-an-expert over the mean, worst layer: counted by the
program's router probe on the first batch at the seeded weights.

Not printed in a rehearsal, though it is a count: ``tests/
test_rehearsal.py`` lists the counts a rehearsal may print, and that
file is not this PR's to edit (PERF.md, Open questions)."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    return (ctx["facts"].get("router") or {}).get("load_imbalance")
