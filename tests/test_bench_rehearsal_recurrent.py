"""``perfbench.run``'s CPU rehearsals, one file a group of about equal
cost (``helpers.REHEARSALS``, ROADMAP D14): the Ling-3.0, SDAR, Nemotron-3 and MiMo-V2.5 cells' and the sweep's."""

import pytest

from helpers import REHEARSALS, check_rehearsal


@pytest.mark.parametrize("cell,trace", REHEARSALS["recurrent"])
def test_benchmark_rehearsal_is_correct_and_prints_counts_only(cell, trace):
    check_rehearsal(cell, trace)
