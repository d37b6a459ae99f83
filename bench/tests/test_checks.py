"""The check that decides `correct`, driven through a whole run at a CPU
size: the program passes; the float8 control put in its place does not,
nor does the program with a fault planted in its timed path: a train step
that returns its state unchanged, a train step over half of the batch, and
a served token altered where it is produced.  The cells run on one chip,
so there is no exchange between chips to leave out."""
import time

import pytest

from bench import harness
from bench.tests.small import SMALL_SEED, small_cell


def _run(config="danube-xlstm", **kw):
    return harness.run(small_cell(config), SMALL_SEED, 2.0, False,
                       t_start=time.perf_counter(), require_tpu=False, **kw)


def test_program_is_correct():
    out = _run()
    assert out["result"]["correct"], out["checks"]
    assert out["result"]["failed"] == 0
    assert list(out["result"])[-1] == "checks"


def test_float8_control_is_not_correct():
    out = _run(fault="fp8_control")
    assert not out["result"]["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["frozen_step", "half_batch",
                                   "altered_token"])
def test_fault_is_not_correct(fault):
    out = _run(fault=fault)
    assert not out["result"]["correct"], out["checks"]

