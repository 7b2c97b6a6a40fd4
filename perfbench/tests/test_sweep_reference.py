"""The sweep's references can fail: a wrong output is caught by the numpy
check and by the on-device one, and the two agree on the payloads."""

import jax
import numpy as np
import pytest

from perfbench import manifest
from perfbench.drivers import collective_sweep as cs


@pytest.fixture(scope="module")
def driver():
    cell = manifest.cell(manifest.load(), "coll_w4_sweep", rehearse=True)
    d = cs.Driver(cell, seed=5, devices=jax.devices()[:4], rehearse=True)
    d.setup()
    yield d
    d.close()


def test_set_up_verified_every_call_and_found_nothing(driver):
    assert driver.correct() and driver.failed == 0
    assert driver.attempted == len(driver.large + driver.small + driver.window)


def test_payload_is_integer_valued_and_what_the_buffers_hold(driver):
    c = driver.large[0]
    for r in range(driver.world):
        want = np.asarray(cs.payload(c.key, r, 1, c.n))
        assert want.dtype == np.float32
        assert np.array_equal(want, np.round(want))
        assert want.min() >= -8 and want.max() < 8 and len(set(want)) > 8
        assert np.array_equal(np.asarray(c.send[r][1].device_array()), want)


@pytest.mark.parametrize("phase,check", [
    ("large", "_differs_on_device"), ("small", "_differs_on_host"),
])
def test_a_wrong_output_is_caught(driver, phase, check):
    calls = getattr(driver, phase)
    turns = [list(c.turn) for c in calls]
    driver._round_blocking(calls)
    assert getattr(driver, check)(calls, turns) == []
    victim = calls[2]                      # reduce_scatter at the first size
    out = victim.recv[3].device_array()
    victim.recv[3].store(out.at[0].add(1.0))
    assert getattr(driver, check)(calls, turns) == [victim.label]


def test_both_references_agree_on_the_large_calls(driver):
    turns = [list(c.turn) for c in driver.large]
    driver._round_blocking(driver.large)
    assert driver._differs_on_host(driver.large, turns) == []
    assert driver._differs_on_device(driver.large, turns) == []
