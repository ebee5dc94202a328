"""A run drives the whole harness on the CPU, past its look for a chip,
with the timed path broken underneath: ``correct`` has to come out false
for each fault a served cell can have, and true without one."""

import time

import pytest

from bench import faults, harness
from conftest import DATA


def _run(workload, fault=None, seed=2**31 + 17):
    return harness.run(harness.RunArgs(workload, seed, 1.5, False),
                       t_start=time.monotonic(), require_chip=False,
                       root=DATA, data=DATA, fault=fault)


@pytest.mark.parametrize("workload", ["tiny-open", "tiny-closed"])
def test_sound_run_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]
    assert list(res["checks"])[0] == "logit_gap"
    assert res["device"]["platform"] == "cpu"
    assert "setup_s" in res["metrics"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_path_is_not_correct(fault):
    res = _run("tiny-closed", faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_no_chip_no_result():
    with pytest.raises(harness.BenchError, match="needs a TPU"):
        harness.run(harness.RunArgs("tiny-open", 1, 1.0, False),
                    t_start=time.monotonic(), root=DATA, data=DATA)
