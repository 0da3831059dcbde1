"""The reduction from trace events to metrics, on a small recorded trace
whose answers are worked out by hand below."""
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"


@pytest.fixture()
def tr():
    return trace.Trace(trace.Events.from_json(DATA.read_text()))


def test_window_and_busy(tr):
    # window: the first span's start (0) to the last span's end (30000 ns)
    assert tr.window_s == pytest.approx(30000e-9)
    # ops inside it: [1000, 3000], the while loop [2400, 5600] and the
    # [2500, 4000] and [3500, 4500] inside it merge into [1000, 5600];
    # then [9000, 9500] and [20000, 24000]; the op at 50000 lies outside:
    # busy 4600 + 500 + 4000 = 9100 ns
    assert tr.busy_s == pytest.approx(9100e-9)
    assert tr.idle_share == pytest.approx(1 - 9100 / 30000)


def test_kernel_and_module_time(tr):
    assert tr.op_seconds(lambda n: "paged_decode" in n) == pytest.approx(1500e-9)
    assert tr.op_seconds(lambda n: "flash_attention" in n) == pytest.approx(500e-9)
    runs, secs = tr.module_runs(lambda n: n.startswith("jit_train_step"))
    assert (runs, secs) == (1, pytest.approx(4000e-9))


def test_breakdown(tr):
    b = tr.breakdown()
    # op kinds by summed time, named from their HLO text: convolution
    # 4000, fusion 2000 + 1000, paged_decode_kernel 1500,
    # flash_attention_kernel 500; the while loop holds traced operations
    # and is left out
    assert b["device_ops"][0] == ["convolution", pytest.approx(4000e-9)]
    assert b["device_ops"][1] == ["fusion", pytest.approx(3000e-9)]
    assert [k for k, _ in b["device_ops"]] == [
        "convolution", "fusion", "paged_decode_kernel", "flash_attention_kernel"]
    # idle gaps: [0,1000] rollout, [5600,9000] rollout (3400 long),
    # [9500,20000] starts as reward (8000-9500) has just ended, so
    # rollout; [24000,30000] trainer
    gaps = b["idle_gaps"]
    assert gaps[0] == ["rollout", pytest.approx(10500e-9)]
    assert gaps[1] == ["trainer", pytest.approx(6000e-9)]
    assert gaps[2] == ["rollout", pytest.approx(3400e-9)]
    assert gaps[3] == ["rollout", pytest.approx(1000e-9)]


@pytest.mark.parametrize("name, kind", [
    ("%fusion.105 = f32[8,12,128]{2,0,1:T(8,128)} fusion(bf16[12,128,1536] %a)",
     "fusion"),
    ("%convolution_select_fusion = f32[4,152064]{1,0:T(4,128)} fusion(%x)",
     "convolution_select_fusion"),
    ("%paged_decode_attention_update.5 = (f32[1,12,128]) custom-call(%t)",
     "paged_decode_attention_update"),
    ("copy.3", "copy"),
])
def test_op_kind_from_hlo_text(name, kind):
    assert trace.op_kind(name) == kind


def test_span_at_prefers_innermost(tr):
    assert tr.span_at(8500) == "reward"
    assert tr.span_at(12000) == "coordinator"
    assert tr.span_at(40000) == "between spans"


def test_events_round_trip(tr):
    again = trace.Events.from_json(tr.events.to_json())
    assert again == tr.events
