"""The controls of ``train_olmoh_t8192_b1``'s check: faults planted one
at a time, each judged by ``train_steps_olmoh.Driver.judge`` itself
against the limits as they are committed, and each has to end NOT correct.

    python3 -m perfbench.controls_olmoh --seed <n> [--seed <m> ...] [--rehearse] [control ...]

One set-up (``Driver.prepare``: the program's logits, first train step's
loss and updated weights), then the sound reference and each control's,
which differ in the reference's side alone: ``fp8_weights``, the reference
from weights rounded to e5m2, the nearest precision below bf16;
``unchanged_state``, the first step's update thrown away; and ways of
getting a layer wrong (``perfbench/reference/olmo_hybrid.py`` names them: a
norm BEFORE the sub-layers instead of after, beta without its 2, no decay, a
sigmoid output gate, no convolution, the full layer without its QK-norm or
with a rope).  A line a control: ``correct``, the problems, the numbers
judged.  Exit 0 where the sound reference ends correct and every control
does not.  ``perfbench/tests/test_olmoh_cell.py`` runs it rehearsed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CONTROLS = {
    "fp8_weights": dict(fp8_weights=True),
    "unchanged_state": dict(unchanged_state=True),
    "pre_norm": dict(pre_norm=True),
    "beta_without_its_2": dict(delta_how=dict(beta_scale=1.0)),
    "no_decay": dict(delta_how=dict(no_decay=True)),
    "sigmoid_gate": dict(delta_how=dict(sigmoid_gate=True)),
    "no_conv": dict(delta_how=dict(no_conv=True)),
    "no_qk_norm": dict(full_how=dict(no_qk_norm=True)),
    "rope_on": dict(full_how=dict(rope_theta=10000.0)),
}
CELL = "train_olmoh_t8192_b1"
_JUDGED = ("row_median", "rel_rms", "max_abs", "loss_rel", "core_calls",
           "update_timed_worst", "update_timed_worst_leaf",
           "update_timed_leaves", "update_probe_worst",
           "update_probe_worst_leaf")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench.controls_olmoh")
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
    from perfbench.drivers.train_steps_olmoh import Driver

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
