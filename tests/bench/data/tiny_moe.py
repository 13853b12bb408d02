"""A sparse-expert decoder with windowed and full attention layers,
shaped like Mellum2 (JetBrains/Mellum2-12B-A2.5B-Instruct) at a test's
size, written as an architecture module (the contract of
``bench/archs/__init__.py``). ``layer_types`` gives each layer's
attention: ``sliding_attention`` reads the last ``sliding_window`` keys,
``full_attention`` the whole causal context. Every layer's MLP is
``num_experts`` SwiGLU experts of width ``moe_intermediate_size`` under
a linear router that sends each token to ``num_experts_per_tok`` of
them."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np

# source key -> the program's ModelConfig field
PROGRAM_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "moe_intermediate_size": "d_ff",
    "num_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "torch_dtype": "dtype",
}


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_ff: int
    vocab: int
    tied: bool
    windows: Tuple[int, ...]        # per layer; 0 = full attention

    @classmethod
    def of(cls, model: Dict) -> "Dims":
        if set(model["mlp_layer_types"]) != {"sparse"}:
            raise ValueError("every layer's MLP has to be sparse")
        windows = tuple(model["sliding_window"]
                        if t == "sliding_attention" else 0
                        for t in model["layer_types"])
        if len(windows) != model["num_hidden_layers"]:
            raise ValueError("one layer type per layer")
        return cls(model["num_hidden_layers"], model["hidden_size"],
                   model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"],
                   model["num_experts"], model["num_experts_per_tok"],
                   model["moe_intermediate_size"], model["vocab_size"],
                   bool(model["tie_word_embeddings"]), windows)

    def layer_matmul_params(self, layer: int) -> int:
        """Projections, the router, and the experts a token is routed
        to."""
        return (self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
                + self.d * self.experts
                + self.top_k * 3 * self.d * self.expert_ff)

    def keys_read(self, layer: int, pos):
        w = self.windows[layer]
        return np.minimum(pos + 1, w) if w else pos + 1

    def weight_shapes(self) -> Dict:
        L, d, H, K, D = (self.layers, self.d, self.heads, self.kv_heads,
                         self.head_dim)
        E, F, V = self.experts, self.expert_ff, self.vocab
        embed = {"tok": (V, d), "final_norm": (d,)}
        if not self.tied:
            embed["head"] = (d, V)
        layer = {"norm1": (L, d),
                 "attn": {"wq": (L, d, H, D), "wk": (L, d, K, D),
                          "wv": (L, d, K, D), "wo": (L, H, D, d)},
                 "norm2": (L, d),
                 "ffn": {"router": (L, d, E), "wg": (L, E, d, F),
                         "wu": (L, E, d, F), "wd": (L, E, F, d)}}
        return {"embed": embed, "stack": {"p0": layer}}

    def scale(self, path: str) -> float:
        if path.endswith("tok"):
            return 0.02
        if path.endswith("wo"):
            return (self.heads * self.head_dim) ** -0.5
        if path.endswith("wd"):
            return self.expert_ff ** -0.5
        return self.d ** -0.5   # wq, wk, wv, router, wg, wu, head


def program_config(conf: Dict):
    """The registry entry ``program_arch`` with the model keys applied.
    The program's ModelConfig holds one attention span for every layer,
    so a window shorter than ``max_context`` cannot be stated."""
    import dataclasses
    from repro.configs import get_config
    window = conf["model"]["sliding_window"]
    if window < conf["max_context"]:
        raise ValueError(f"{conf['name']}: windowed layers of {window} "
                         f"under max_context {conf['max_context']}")
    over = {PROGRAM_KEYS[k]: v for k, v in conf["model"].items()
            if k in PROGRAM_KEYS}
    return dataclasses.replace(get_config(conf["program_arch"]), **over,
                               sliding_window=0)
