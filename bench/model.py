"""A configuration file (``bench/configs/<config>.json``) and the
weights the benchmark makes for it.

The file keeps the source's own key names (``hidden_size``,
``num_hidden_layers``, ...) under ``model``. ``program_config`` maps
them onto the program's ``ModelConfig``; ``make_weights`` draws the
weights from the seed, on the device, in one jitted call, in the type
they are served in. The weights are the benchmark's: the reference
takes them from here as well, never from the program.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

# source key -> the program's ModelConfig field
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "torch_dtype": "dtype",
}


@dataclass(frozen=True)
class Dims:
    """The sizes the reference and the work counts need."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float

    @classmethod
    def of(cls, model: Dict) -> "Dims":
        return cls(model["num_hidden_layers"], model["hidden_size"],
                   model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"],
                   model["intermediate_size"], model["vocab_size"],
                   bool(model["tie_word_embeddings"]),
                   float(model["rope_theta"]), float(model["rms_norm_eps"]))

    def layer_matmul_params(self) -> int:
        """Weights a token meets in one layer's projections and MLP."""
        return (self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
                + 3 * self.d * self.ff)


def load_config(name: str) -> Dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def program_config(conf: Dict):
    """The program's ModelConfig for this configuration: its registry
    entry ``program_arch`` with every model key of the file applied
    (sliding window: the engine serves full attention, and a window of
    at least ``max_context`` changes nothing)."""
    import dataclasses
    from repro.configs import get_config
    base = get_config(conf["program_arch"])
    over = {PROGRAM_KEYS[k]: v for k, v in conf["model"].items()
            if k in PROGRAM_KEYS}
    window = conf["model"].get("sliding_window") or 0
    if window and window < conf["max_context"]:
        raise ValueError(f"{conf['name']}: sliding window {window} is "
                         f"shorter than max_context {conf['max_context']}")
    return dataclasses.replace(base, **over, sliding_window=0)


def weight_shapes(dims: Dims) -> Dict:
    """Leaf shapes, in the layout the program's decoder takes (layers
    stacked on axis 0, projections head-factored)."""
    L, d, H, K, D, F, V = (dims.layers, dims.d, dims.heads, dims.kv_heads,
                           dims.head_dim, dims.ff, dims.vocab)
    embed = {"tok": (V, d), "final_norm": (d,)}
    if not dims.tied:
        embed["head"] = (d, V)
    layer = {"norm1": (L, d),
             "attn": {"wq": (L, d, H, D), "wk": (L, d, K, D),
                      "wv": (L, d, K, D), "wo": (L, H, D, d)},
             "norm2": (L, d),
             "ffn": {"wg": (L, d, F), "wu": (L, d, F), "wd": (L, F, d)}}
    return {"embed": embed, "stack": {"p0": layer}}


def _scale(path: str, dims: Dims) -> float:
    """Standard deviation of a leaf: 0.02 for the token embedding,
    1/sqrt(fan-in) for projections (the contracted dims), and norm
    gains are drawn around 1 (see ``make_weights``)."""
    if path.endswith("tok"):
        return 0.02
    if path.endswith("wo"):
        return (dims.heads * dims.head_dim) ** -0.5
    if path.endswith("wd"):
        return dims.ff ** -0.5
    return dims.d ** -0.5           # wq, wk, wv, wg, wu, head


def make_weights(dims: Dims, seed: int, dtype: str = "bfloat16",
                 device=None):
    """All weights from ``seed`` in one jitted call on ``device`` (the
    default device when None). Each leaf has its own key, folded from
    its path, so one leaf's values do not depend on the others."""
    import zlib
    import jax
    import jax.numpy as jnp
    from bench.seeds import jax_key

    shapes = weight_shapes(dims)
    dt = jnp.dtype(dtype)

    def build(key):
        def walk(tree, path):
            if isinstance(tree, dict):
                return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            z = jax.random.normal(k, tree, dt)
            if "norm" in path:
                return (1 + 0.1 * z).astype(dt)
            return (z * _scale(path, dims)).astype(dt)
        return walk(shapes, "")

    kw = {}
    if device is not None:
        from jax.sharding import SingleDeviceSharding
        kw["out_shardings"] = SingleDeviceSharding(device)
    return jax.jit(build, **kw)(jax_key(seed))
