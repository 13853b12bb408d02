"""The readers of the program's own spans and counters on a hand-made
one-chip profile (``data/program_spans.pbtxt``, counted by hand in its
header), and their silence where the program has none."""

from pathlib import Path

import pytest

from bench import program_spans
from bench import trace as tr
from bench.metrics import (decode_padded_lane_share, device_idle_share,
                           e2_schedule_ms, engine_host_idle_share,
                           engine_host_ms)

DATA = Path(__file__).parent / "data"
NS = 1e-9
SPAN_READERS = (engine_host_ms, engine_host_idle_share, e2_schedule_ms)


class _Run:
    def __init__(self, trace, stats0=None, stats1=None):
        self.trace = trace
        self.stats0 = stats0 or {0: {}}
        self.stats1 = stats1 or {0: {}}

    def stats_delta(self):
        return {k: sum(self.stats1[i][k] - self.stats0[i][k]
                       for i in self.stats1) for k in self.stats1[0]}


def _traced(name, tmp_path, monkeypatch):
    """A run traced into its own directory, as ``bench/run.py`` leaves
    it: the profile file under the trace directory and ``run.trace``
    read from it."""
    from jax.profiler import ProfileData
    text = (DATA / name).read_text()
    f = tmp_path / "plugins" / "profile" / "1" / "host.xplane.pb"
    f.parent.mkdir(parents=True)
    f.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    return _Run(tr.from_profile(ProfileData.from_text_proto(text)))


@pytest.fixture
def run(tmp_path, monkeypatch):
    return _traced("program_spans.pbtxt", tmp_path, monkeypatch)


def test_spans_are_the_programs_clipped_to_the_window(run):
    spans = program_spans.of(run)
    assert all(n.startswith("preble.") for n, _, _ in spans)
    assert min(s for _, s, _ in spans) == pytest.approx(1000 * NS)
    assert max(e for _, _, e in spans) == pytest.approx(21000 * NS)
    eng = program_spans.engines(spans)
    assert list(eng) == ["0"]
    assert len(eng["0"]["step"]) == 5 and len(eng["0"]["fetch"]) == 4
    assert [len(c) for c in program_spans.nest(eng["0"]["step"],
                                               eng["0"]["fetch"])] == \
        [1, 1, 0, 1, 1]


def test_engine_host_ms(run):
    assert engine_host_ms.read(run) == pytest.approx(2000 * NS * 1e3)


def test_engine_host_idle_share_is_part_of_device_idle(run):
    share = engine_host_idle_share.read(run)
    assert share == pytest.approx(31.5)
    assert device_idle_share.read(run) == pytest.approx(60.5)
    assert share <= device_idle_share.read(run)


def test_e2_schedule_ms(run):
    assert e2_schedule_ms.read(run) == pytest.approx(500 * NS * 1e3)


def test_interval_arithmetic():
    spans = [("s", 0.0, 10.0), ("s", 20.0, 30.0)]
    holes = [("f", 2.0, 4.0), ("f", 8.0, 22.0), ("f", 25.0, 26.0)]
    assert program_spans.minus(spans, holes) == [
        (0.0, 2.0), (4.0, 8.0), (22.0, 25.0), (26.0, 30.0)]
    assert program_spans.overlap_s([(0.0, 5.0), (6.0, 9.0)],
                                   [(4.0, 7.0)]) == pytest.approx(2.0)


def test_decode_padded_lane_share():
    zero = {0: {"decode_only_lanes": 8, "decode_only_padded_lanes": 2}}
    later = {0: {"decode_only_lanes": 48, "decode_only_padded_lanes": 12}}
    assert decode_padded_lane_share.read(_Run(None, zero, later)) == \
        pytest.approx(25.0)
    assert decode_padded_lane_share.read(_Run(None, later, later)) is None


def test_readers_are_silent_without_the_programs_spans(tmp_path,
                                                       monkeypatch):
    # the benchmark's own spans only, as a program without its own
    # spans and counters leaves a trace
    run = _traced("two_chips.pbtxt", tmp_path, monkeypatch)
    assert program_spans.of(run) is None
    for reader in SPAN_READERS:
        assert reader.read(run) is None
    assert decode_padded_lane_share.read(_Run(None, {0: {"x": 0}},
                                              {0: {"x": 1}})) is None


def test_readers_are_silent_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    for reader in SPAN_READERS:
        assert reader.read(_Run(None)) is None
    run = _Run(tr.from_events((0.0, 1.0), {}, []))
    for reader in SPAN_READERS:        # traced, but no profile file
        assert reader.read(run) is None
