"""The listed danube round cell judged by its own limits, set on the chip at
the cell's size, and driven here at ``test_correct``'s tiny danube shape: a
sound run is correct, a run with a round fault planted in its timed path is
not, and the float8 control reads above the program."""
import pytest

from chipbench import readings
from chipbench.run import Context
from chipbench.tests.test_correct import _altered_label, _cell, _execute, _half_batch, _unchanged_state

CELL = "h2o-danube-1.8b.round.stld50.b16"


def test_sound_run_is_correct():
    result = _execute(_cell(CELL))
    assert result["correct"], result["checks"]


@pytest.mark.parametrize(
    "fault", [_unchanged_state, _half_batch, _altered_label],
    ids=["state-unchanged", "half-batch", "label-altered"],
)
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = _execute(_cell(CELL))
    assert not result["correct"], result["checks"]


def test_control_reads_above_the_program():
    got = readings.round_readings(Context(_cell(CELL), 5, 1.0, False), ["program", "fp8"])
    assert got["program"]["correct"], got
    assert got["fp8"]["loss_gap"] > 3 * got["program"]["loss_gap"]
    assert got["fp8"]["grad_gap"] > got["program"]["grad_gap"]
