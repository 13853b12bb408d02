"""The benchmark's tests import ``bench`` from the repository's root."""

import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


import pytest  # noqa: E402

# A reduced configuration and mix that a CPU test run holds: the
# program's smollm-360m entry narrowed to two float32 layers.
TINY_CONFIG = {
    "name": "tiny", "program_arch": "smollm-360m",
    "reference": "dense_decoder",
    "model": {"num_hidden_layers": 2, "hidden_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "intermediate_size": 256, "vocab_size": 2048,
              "tie_word_embeddings": True, "rope_theta": 10000.0,
              "rms_norm_eps": 1e-5, "torch_dtype": "float32"},
    "max_context": 512, "pool_tokens": 4096}
TINY_MIX = {
    "name": "tiny", "system_len": 32,
    "segments": {"count": 4, "zipf": 1.1,
                 "len": {"mean": 64, "std": 16, "min": 16, "max": 112}},
    "unique_len": {"mean": 16, "std": 4, "min": 4, "max": 28},
    "output_len": {"mean": 12, "std": 4, "min": 2, "max": 24},
    "arrivals": {"process": "poisson"}}


@pytest.fixture
def tiny_peaks():
    return {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def tiny_cell():
    """A Cell of the benchmark at CPU size (float32, where the program
    and the reference agree exactly), with the real metric lists."""
    import json
    from bench import run
    spec = json.loads((Path(ROOT) / "BENCHMARK.json").read_text())
    return run.Cell(
        "tiny", 1, dict(TINY_CONFIG), dict(TINY_MIX),
        {"replicas": 1, "rate_rps": 4.0, "ramp_s": 1,
         "drain_s": 20,
         "check": {"sample_tokens": 80, "limits": {"widest_gap": 1e-3}}},
        [m["name"] for m in spec["end_to_end"]],
        [m["name"] for m in spec["per_layer"]])
