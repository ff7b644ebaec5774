"""The suite's own two guards (tests/conftest.py): the limit every case
runs under, and the critical-path lines at the end of a run."""
import time
import types

import pytest

from conftest import CASE_LIMIT_S, case_limit, suite_summary


def test_case_past_its_limit_fails_alone_with_its_name():
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as caught:
        with case_limit("tests/test_x.py::test_sleeps", seconds=0.05):
            time.sleep(5)
    assert time.monotonic() - t0 < 5
    msg = str(caught.value)
    assert "tests/test_x.py::test_sleeps ran past its limit of 0.05 s" in msg
    assert "time.sleep(5)" in msg       # the stack the case was in
    # the limit is disarmed with the case: the run goes on
    time.sleep(0.1)


def test_case_inside_its_limit_is_left_alone():
    with case_limit("tests/test_x.py::test_quick", seconds=0.05):
        pass
    time.sleep(0.1)
    assert CASE_LIMIT_S == 180.0


def test_summary_arithmetic_on_made_up_reports():
    def rep(nodeid, duration):
        return types.SimpleNamespace(nodeid=nodeid, duration=duration)
    lines = suite_summary([
        rep("tests/test_a.py::test_one", 0.5),      # set-up
        rep("tests/test_a.py::test_one", 40.0),     # call
        rep("tests/test_a.py::test_two", 2.0),
        rep("tests/test_b.py::test_three[x]", 30.25),
    ], top=1)
    assert lines == [
        "test-seconds 73 in 3 cases of 2 files",
        "longest files (s / cases): tests/test_a.py 42 / 2",
        "longest case: tests/test_a.py::test_one 40 s",
    ]
    assert suite_summary([]) == []
