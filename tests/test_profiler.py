"""Profiler tests.

Reference coverage model: test/legacy_test/test_profiler*.py and the
profiler_statistic unit tests (SURVEY.md §5).
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import (Profiler, ProfilerState, ProfilerTarget,
                                 RecordEvent, export_chrome_tracing,
                                 make_scheduler)


def test_make_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                           skip_first=1)
    states = [sched(i) for i in range(6)]
    assert states[0] == ProfilerState.CLOSED       # skip_first
    assert states[1] == ProfilerState.CLOSED
    assert states[2] == ProfilerState.READY
    assert states[3] == ProfilerState.RECORD
    assert states[4] == ProfilerState.RECORD_AND_RETURN
    assert states[5] == ProfilerState.CLOSED       # repeat exhausted


def test_record_event_noop_when_closed():
    ev = RecordEvent("idle")
    ev.begin()
    ev.end()  # no profiler active: nothing recorded, no error


def test_profiler_records_ops_and_exports(tmp_path):
    with Profiler(targets=[ProfilerTarget.CPU]) as prof:
        x = paddle.randn([8, 8])
        y = paddle.matmul(x, x)
        with RecordEvent("user_block"):
            (y + 1).sum()
    names = {e.name for e in prof.events}
    assert "matmul" in names
    assert "user_block" in names

    path = prof.export(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    assert any(e["name"] == "matmul" for e in data["traceEvents"])

    table = prof.summary()
    assert "matmul" in table and "Calls" in table


def test_profiler_step_scheduler_windows(tmp_path):
    flushed = []

    def handler(prof):
        flushed.append(len(prof.events))

    prof = Profiler(scheduler=make_scheduler(closed=1, ready=0, record=1,
                                             repeat=2),
                    on_trace_ready=handler)
    prof.start()
    for _ in range(4):
        paddle.ones([2]).sum()
        prof.step()
    prof.stop()
    assert len(flushed) >= 1


def test_export_chrome_tracing_handler(tmp_path):
    with Profiler(on_trace_ready=export_chrome_tracing(str(tmp_path))) as p:
        paddle.ones([2]) + 1
    files = list(tmp_path.glob("*.paddle_trace.json"))
    assert len(files) == 1


def test_ops_not_recorded_when_profiler_off():
    before = len(profiler._ACTIVE)
    paddle.ones([2]) + 1
    assert len(profiler._ACTIVE) == before == 0
