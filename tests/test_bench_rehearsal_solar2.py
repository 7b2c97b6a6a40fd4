"""``perfbench.run``'s CPU rehearsals, one file a group of about equal
cost (``helpers.REHEARSALS``, ROADMAP D14): the Solar Open 2 cell's (the dearest), a StarCoder cell's and the Olmo Hybrid cell's."""

import pytest

from helpers import REHEARSALS, check_rehearsal


@pytest.mark.parametrize("cell,trace", REHEARSALS["solar2"])
def test_benchmark_rehearsal_is_correct_and_prints_counts_only(cell, trace):
    check_rehearsal(cell, trace)
