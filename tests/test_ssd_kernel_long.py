"""The Mamba-2 core's kernels against their oracles at three chunks and at
five with a padded tail: the longer half of
``tests/test_ssd_kernel.py``'s parametrised case, in a file of its own so
that two xdist workers share what was tier-1's longest file and its tail
under ``--dist loadfile`` (ROADMAP D14).  Same function, same ids."""

import pytest

from test_ssd_kernel import check_against_the_oracles, exact  # noqa: F401


@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("length", [384, 520])
def test_kernels_against_the_recurrence_and_the_xla_form(length, groups, exact):
    check_against_the_oracles(length, groups)
