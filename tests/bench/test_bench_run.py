"""The harness end to end on a reduced CPU cluster (the chip look
skipped), the result line's format, and the refusals of the command
line."""

import json
import re
from pathlib import Path

import jax
import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_cell_finds_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and 0 < len(m["layer"]) <= 200
    for w in spec["workloads"] + spec["configs"]:
        assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in spec["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert "setup_s" in cell.end_to_end
        assert cell.per_layer and len(cell.end_to_end) >= 2
        # each per-layer metric moves an end-to-end metric its cell reports
        moves = {m["name"]: m["moves"] for m in spec["per_layer"]}
        assert {moves[n] for n in cell.per_layer} <= set(cell.end_to_end)
        assert cell.params["replicas"] <= w["chips"]


def test_command_line_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "smollm-360m.toolbench", "--seed",
                  str(2 ** 31 + 3), "--seconds", "1"])
    assert e.value.code == 2
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_refused():
    with pytest.raises(SystemExit):
        run.load_peaks("TPU v99")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no-such-model.toolbench")


@pytest.mark.parametrize("trace", [0, 1])
def test_one_run_on_a_reduced_cluster(tiny_cell, tiny_peaks, trace):
    devs = jax.devices()[:1]
    out = run.execute(tiny_cell, 2 ** 31 + 99, 2.0, bool(trace),
                      devices=devs, peaks=tiny_peaks)
    line = run.result_line(out, tiny_cell, devs)
    assert list(line)[-1] == "checks"
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    if trace:
        # host-side per-layer metrics; the CPU has no device plane
        assert {"arrival_lag_p99_ms", "prompt_cached_share",
                "queue_wait_p90_s"} <= set(line["metrics"])
        assert "window_s" in line["device"] and "breakdown" in line
    else:
        assert set(line["metrics"]) == set(tiny_cell.end_to_end)
        assert line["metrics"]["output_tokens_per_s"]["unit"] == "tokens/s"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(line)


def test_host_watch_keeps_the_longest_pass_and_the_collector():
    import gc
    import time
    from bench.harness import HostWatch
    with HostWatch() as w:
        m = w.mark()
        gc.collect()
        w.record(m, 1.0)
        collect = w.longest["wall_s"]
        assert w.longest["gc_s"] > 0 and w.gc_full >= 1
        m = w.mark()
        time.sleep(max(0.05, 2 * collect))
        w.record(m, 2.0)
    assert w.longest["at_s"] == 2.0
    assert w.longest["gc_s"] == 0
    assert w.longest["cpu_s"] < 0.5 * w.longest["wall_s"]   # asleep
    assert w._on_gc not in gc.callbacks
