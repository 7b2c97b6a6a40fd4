"""``perfbench.run``'s CPU rehearsals, one file a group of about equal
cost (``helpers.REHEARSALS``, ROADMAP D14): the DeepSeek-V2, Trinity and OLMoE cells', the sweep's and
``train_t1024_b8``'s traced ones."""

import pytest

from helpers import REHEARSALS, check_rehearsal


@pytest.mark.parametrize("cell,trace", REHEARSALS["rest"])
def test_benchmark_rehearsal_is_correct_and_prints_counts_only(cell, trace):
    check_rehearsal(cell, trace)
