"""``perfbench/controls_nemotron3.py``, rehearsed: a file of its own
beside ``perfbench.run``'s rehearsals (``helpers.REHEARSALS``, ROADMAP
D14), each about a quarter of what ONE worker used to run."""

import json

from helpers import nice_child


def test_nemotron3_controls_each_end_not_correct():
    """The cell's own ``judge`` at its committed limits ends correct on
    the sound reference and not correct on every planted fault; a state
    the steps left unchanged is past the update's two limits alone."""
    from perfbench import controls_nemotron3 as controls

    proc = nice_child("perfbench.controls_nemotron3", "--seed", "5",
                      "--rehearse", timeout=900)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    *lines, last = map(json.loads, proc.stdout.strip().splitlines())
    assert last == {"controls": "ok", "wrong": []}
    assert [l["control"] for l in lines] == ["sound", *controls.CONTROLS]
    by_name = {l["control"]: l for l in lines}
    assert all(l["correct"] == (name == "sound") for name, l in by_name.items())
    unchanged = by_name["unchanged_state"]["check"]
    assert unchanged["update_timed_worst"] == 1.0
    assert abs(unchanged["update_probe_worst"] - 1.0) < 1e-6
    assert len(by_name["unchanged_state"]["problems"]) == 1
    assert "update" in by_name["unchanged_state"]["problems"][0]
