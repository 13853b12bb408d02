"""The trace reduction on a hand-made two-chip profile whose busy
time, idle gaps and program times are counted by hand in the file's
header."""

from pathlib import Path

import pytest

from bench import trace as tr
from bench.metrics import decode_step_ms, device_idle_share, mixed_step_ms

NS = 1e-9


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    text = (Path(__file__).parent / "data" / "two_chips.pbtxt").read_text()
    return tr.from_profile(ProfileData.from_text_proto(text))


class _Run:
    def __init__(self, trace):
        self.trace = trace


def test_window_is_the_window_span(trace):
    assert trace.lo == pytest.approx(1000 * NS)
    assert trace.window_s == pytest.approx(10000 * NS)
    assert [c.name for c in trace.chips] == ["/device:TPU:0",
                                             "/device:TPU:1"]


def test_busy_is_the_union_of_clipped_ops(trace):
    c0, c1 = trace.chips
    assert tr.union_s(c0.ops) == pytest.approx(4000 * NS)
    assert tr.union_s(c1.ops) == pytest.approx(3500 * NS)
    assert tr.busy_s(trace) == pytest.approx(3750 * NS)
    assert tr.idle_share(trace) == pytest.approx(0.625)
    assert device_idle_share.read(_Run(trace)) == pytest.approx(62.5)


def test_program_time_per_call(trace):
    assert mixed_step_ms.read(_Run(trace)) == pytest.approx(3000 * NS * 1e3)
    assert decode_step_ms.read(_Run(trace)) == pytest.approx(1000 * NS * 1e3)


def test_gaps_labelled_by_innermost_host_span(trace):
    assert tr.gaps(trace.chips[0], trace.lo, trace.hi) == [
        pytest.approx((4000 * NS, 6000 * NS)),
        pytest.approx((7000 * NS, 11000 * NS))]
    b = tr.breakdown(trace)
    assert [k for k, _ in b["idle_gaps"]] == [
        "bench.engine_step.0", "bench.sleep", "bench.form_batch.0"]
    assert [v for _, v in b["idle_gaps"]] == pytest.approx(
        [6500 * NS, 4000 * NS, 2000 * NS])
    ops = dict(b["device_ops"])
    # fusion.1 (2000 + 1500), fusion.2 (2000) and fusion.3 (2000)
    assert ops["fusion"] == pytest.approx(7500 * NS)
    assert ops["paged_decode_kernel"] == pytest.approx(1000 * NS)


def test_op_kind_drops_numbers_and_layouts():
    assert tr.op_kind("%copy.7.remat = bf16[8,128]{1,0:T(8,128)} copy("
                      "bf16[8,128]{0,1} %p.1)") == "copy.remat bf16[8,128]"
    assert tr.op_kind("%custom-call.3 = (f32[4,2]{1,0}, f32[4]{0}) "
                      "custom-call(f32[4] %x)") == \
        "custom-call (f32[4,2],f32[4])"


def test_label_outside_any_span():
    assert tr.label(5.0, [("bench.sleep", 1.0, 2.0)]) == tr.NO_SPAN


def test_no_window_span_is_an_error():
    from jax.profiler import ProfileData
    text = 'planes { id: 1 name: "/host:CPU" }'
    with pytest.raises(ValueError):
        tr.from_profile(ProfileData.from_text_proto(text))


def test_no_trace_reads_nothing():
    assert mixed_step_ms.read(_Run(None)) is None
    assert device_idle_share.read(_Run(None)) is None
