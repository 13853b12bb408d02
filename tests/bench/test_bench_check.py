"""The correctness check: its controls (the reference in fp8 and in
int8) must read as not correct, and a run whose timed path alters
tokens where they are produced must come out ``correct: false``. Both
at a size a CPU test run holds; on a TPU the same comparison runs at
the cells' sizes (``bench/control.py``)."""

import json

import jax
import numpy as np
import pytest

from bench import check, control, run


def test_control_fails_where_the_program_passes(tiny_cell, tiny_peaks,
                                                monkeypatch, capsys):
    # bench/control.py with the chip look skipped, at the tiny cell
    monkeypatch.setattr(run, "load_cell", lambda name: tiny_cell)
    monkeypatch.setattr(run, "find_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(run, "load_peaks", lambda kind: tiny_peaks)
    assert control.main(["--workload", "tiny", "--seeds",
                         str(2 ** 32 + 17), "--seconds", "4"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limit = tiny_cell.params["check"]["limits"]["widest_gap"]
    assert row["correct"] is True
    assert row["tokens"] >= tiny_cell.params["check"]["sample_tokens"]
    assert row["program_widest"] <= limit
    assert row["fp8_widest"] > limit and row["fp8_correct"] is False
    assert row["int8_widest"] > limit and row["int8_correct"] is False


def test_altered_tokens_are_caught(tiny_cell, tiny_peaks, monkeypatch):
    from repro.serving.engine import Engine
    fetch = Engine._fetch_result
    calls = [0]

    def altered(self, nxt):
        out = np.array(fetch(self, nxt))
        calls[0] += 1
        if calls[0] % 5 == 0:          # every fifth step's tokens
            out = (out + 1) % tiny_cell.config["model"]["vocab_size"]
        return out

    monkeypatch.setattr(Engine, "_fetch_result", altered)
    out = run.execute(tiny_cell, 2 ** 31 + 5, 2.0, False,
                      devices=jax.devices()[:1], peaks=tiny_peaks)
    assert out["correct"] is False
    c = out["checks"]["widest_gap"]
    assert c["value"] > c["limit"]


def test_sample_holds_the_longest_and_enough_tokens():
    from types import SimpleNamespace as NS
    from bench.harness import Flight
    from bench.traffic import Prompt

    def fl(n_prompt, n_out, done=1.0):
        f = Flight(Prompt(tuple(range(n_prompt)), n_out, 0), 0.0)
        f.request = NS(output_tokens=[1] * n_out)
        f.done = done
        return f

    fs = [fl(10, 5), fl(50, 5), fl(20, 30), fl(99, 50, done=None)]
    s = check.sample(fs, seed=3, min_tokens=12)
    assert s[0] is fs[1] or s[0] is fs[2]
    assert max(fs[:3], key=lambda f: len(f.prompt.tokens)
               + len(f.request.output_tokens)) is s[0]
    assert sum(len(f.request.output_tokens) for f in s) >= 12
    assert fs[3] not in s
    assert check.sample(fs[3:], seed=3, min_tokens=1) == []


def test_verdict():
    ok = {"a": {"value": 0.1, "limit": 0.2}, "b": {"value": 0, "limit": 0}}
    assert check.verdict(ok)
    ok["b"]["value"] = 1
    assert not check.verdict(ok)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_and_controls_run_tied_and_untied(tied):
    from bench.archs.dense_decoder import Dims
    from bench.model import make_weights
    from bench.references import dense_decoder
    dims = Dims(layers=2, d=32, heads=4, kv_heads=2, head_dim=8, ff=64,
                vocab=64, tied=tied, rope_theta=1e4, eps=1e-5)
    w = make_weights(dims, 5, "float32")
    toks = np.arange(1024, dtype=np.int32) % 64
    pos = np.arange(10, 138, dtype=np.int32)
    ref = dense_decoder.compiled(dims)(w, toks, pos)
    assert ref.shape == (128, 64) and bool(np.isfinite(ref).all())
    for q in ("fp8", "int8"):
        lg = dense_decoder.compiled(dims, quant=q)(w, toks, pos)
        assert lg.shape == ref.shape and bool(np.isfinite(lg).all())
        assert not np.allclose(lg, ref)
