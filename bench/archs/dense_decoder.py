"""Dense pre-norm decoder (Llama / Mistral / SmolLM): in every layer the
same grouped-query attention over the whole causal context and the same
SwiGLU MLP. The module contract is ``bench/archs/__init__.py``'s."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

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
    """The sizes the weights, the reference and the work counts need."""
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

    def layer_matmul_params(self, layer: int) -> int:
        """Weights a token meets in a layer's projections and MLP: the
        same in every layer."""
        return (self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
                + 3 * self.d * self.ff)

    def keys_read(self, layer: int, pos):
        """Every layer attends over the whole causal context."""
        return pos + 1

    def weight_shapes(self) -> Dict:
        """Leaf shapes, in the layout the program's decoder takes (layers
        stacked on axis 0, projections head-factored)."""
        L, d, H, K, D, F, V = (self.layers, self.d, self.heads,
                               self.kv_heads, self.head_dim, self.ff,
                               self.vocab)
        embed = {"tok": (V, d), "final_norm": (d,)}
        if not self.tied:
            embed["head"] = (d, V)
        layer = {"norm1": (L, d),
                 "attn": {"wq": (L, d, H, D), "wk": (L, d, K, D),
                          "wv": (L, d, K, D), "wo": (L, H, D, d)},
                 "norm2": (L, d),
                 "ffn": {"wg": (L, d, F), "wu": (L, d, F), "wd": (L, F, d)}}
        return {"embed": embed, "stack": {"p0": layer}}

    def scale(self, path: str) -> float:
        """0.02 for the token embedding, 1/sqrt(fan-in) for projections
        (the contracted dims)."""
        if path.endswith("tok"):
            return 0.02
        if path.endswith("wo"):
            return (self.heads * self.head_dim) ** -0.5
        if path.endswith("wd"):
            return self.ff ** -0.5
        return self.d ** -0.5           # wq, wk, wv, wg, wu, head


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
