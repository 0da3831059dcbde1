"""``decode_device_ms`` on small recorded traces, with the answer worked
out by hand."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import metrics, trace

DATA = Path(__file__).resolve().parent / "data"


def _ctx(name):
    events = trace.Events.from_json((DATA / name).read_text())
    return SimpleNamespace(trace=trace.Trace(events))


def test_device_time_per_decode_dispatch():
    # inside the window [0, 30000]: rollout_decode runs at 1000 (300 ns),
    # 4000 (200) and 7000 (250), two compiled shapes of one program;
    # rollout_sample after each (100, 100, 120). The prefill's sampler
    # (jit_rollout_prefill_sample) is another program, and the decode run
    # at 50000 lies outside the window: (750 + 320) ns over 3 dispatches
    got = metrics.load("decode_device_ms").read(_ctx("trace_programs.json"))
    assert got == pytest.approx(1e3 * 1070e-9 / 3)


def test_none_without_the_named_programs():
    # trace_small.json predates the names: its decode program is jit_step
    reader = metrics.load("decode_device_ms")
    assert reader.read(_ctx("trace_small.json")) is None
    assert reader.read(SimpleNamespace(trace=None)) is None
