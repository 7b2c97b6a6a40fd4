"""The controls of ``train_mimo_t8192_b1``'s check: faults planted one at a
time, each judged by ``train_steps_mimo.Driver.judge`` itself against the
limits as they are committed, and each has to end NOT correct.

    python3 -m perfbench.controls_mimo --seed <n> [--seed <m> ...] [--rehearse] [control ...]

One set-up (``Driver.prepare``: the program's logits, router counters, first
train step's loss and updated weights), then the sound reference and each
control's, which differ in the reference's side alone: ``fp8_weights``, the
reference from weights rounded to e5m2, the nearest precision below bf16;
``unchanged_state``, the first step's update thrown away; and ways of getting
a layer wrong (``perfbench/reference/mimo_v2.py`` names them: no sink, a sink
on the full layers too, the sink with a value, a window one key short or
long, every column of a head rotating or the last 64, the two thetas swapped,
no value scale, the full layers' four KV heads in both kinds).  A line a
control: ``correct``, the problems, the numbers judged.  Exit 0 where the
sound reference ends correct and every control does not.
``perfbench/tests/test_mimo_cell.py`` runs it rehearsed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CONTROLS = {
    "fp8_weights": dict(fp8_weights=True),
    "unchanged_state": dict(unchanged_state=True),
    "no_sink": dict(sink="none"),
    "sink_on_full_layers": dict(sink="full_too"),
    "sink_with_a_value": dict(sink="valued"),
    "window_127": dict(window_off=-1),
    "window_129": dict(window_off=1),
    "all_columns_rotate": dict(rotate="all"),
    "last_columns_rotate": dict(rotate="last"),
    "thetas_swapped": dict(swap_thetas=True),
    "no_value_scale": dict(v_scale=1.0),
    "four_kv_heads_in_both_kinds": dict(pair_kv=True),
}
CELL = "train_mimo_t8192_b1"
_JUDGED = ("row_median", "rel_rms", "max_abs", "loss_rel", "flash_calls",
           "moved_entries", "allowed_entries", "update_timed_worst",
           "update_timed_worst_leaf", "update_timed_leaves",
           "update_probe_worst", "update_probe_worst_leaf",
           "update_probe_sinks")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench.controls_mimo")
    ap.add_argument("--seed", type=int, action="append",
                    help="may be given more than once: a set-up each")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--leaves", action="store_true",
                    help="print each leaf's update readings too")
    ap.add_argument("controls", nargs="*", default=list(CONTROLS),
                    help="default: all; 'sound' alone: no control")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from perfbench import manifest, run

    cell = manifest.cell(manifest.load(), CELL, rehearse=args.rehearse)

    import jax

    if not args.rehearse:
        run._use_compile_cache(manifest.CHECKOUT)
    from perfbench.drivers.train_steps_mimo import Driver

    keys = _JUDGED + (("update",) if args.leaves else ())
    names = ["sound"] + [name for name in args.controls if name != "sound"]
    wrong = []
    for seed in args.seed or [0]:
        driver = Driver(cell, seed, jax.devices()[:1], args.rehearse)
        driver.prepare()
        for name in names:
            driver.problems, driver.failed = [], 0
            driver.judge(**CONTROLS.get(name, {}))
            correct = driver.correct()
            if correct != (name == "sound"):
                wrong.append([seed, name])
            print(json.dumps({
                "control": name, "seed": seed, "correct": correct,
                "problems": driver.problems,
                "check": {k: driver.check[k] for k in keys},
            }), flush=True)
        del driver      # and its three sets of weights on the host
    print(json.dumps({"controls": "wrong" if wrong else "ok", "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
