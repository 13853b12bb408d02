"""A configuration file (``bench/configs/<config>.json``), its
architecture and the weights the benchmark makes for it.

The file keeps the source's own key names (``hidden_size``,
``num_hidden_layers``, ...) under ``model``. Its ``reference`` key names
both the plain reference (``bench/references/<reference>.py``) and the
architecture module (``bench/archs/<reference>.py``, whose contract is
``bench/archs/__init__.py``'s), since the reference runs the weight
tree that module lays out. That module reads the sizes
(``dims_of``), lays out the weights and maps the file onto the
program's ``ModelConfig`` (``program_config``); ``make_weights`` draws
the weights from the seed, on the device, in one jitted call, in the
type they are served in. The weights are the benchmark's: the reference
takes them from here as well, never from the program.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path
from typing import Dict

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load_config(name: str) -> Dict:
    return json.loads((CONFIG_DIR / f"{name}.json").read_text())


def arch(conf: Dict):
    """The configuration's architecture module, named by its
    ``reference``."""
    return importlib.import_module(f"bench.archs.{conf['reference']}")


def dims_of(conf: Dict):
    """The configuration's sizes, as its architecture reads them."""
    return arch(conf).Dims.of(conf["model"])


def program_config(conf: Dict):
    """The program's ModelConfig for this configuration."""
    return arch(conf).program_config(conf)


def make_weights(dims, seed: int, dtype: str = "bfloat16", device=None):
    """All weights of ``dims.weight_shapes()`` from ``seed`` in one
    jitted call on ``device`` (the default device when None). Each leaf
    has its own key, folded from its path, so one leaf's values do not
    depend on the others. Norm gains are drawn around 1, every other
    leaf around 0 with the standard deviation ``dims.scale(path)``."""
    import zlib
    import jax
    import jax.numpy as jnp
    from bench.seeds import jax_key

    shapes = dims.weight_shapes()
    dt = jnp.dtype(dtype)

    def build(key):
        def walk(tree, path):
            if isinstance(tree, dict):
                return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
            k = jax.random.fold_in(key, zlib.crc32(path.encode()))
            z = jax.random.normal(k, tree, dt)
            if "norm" in path:
                return (1 + 0.1 * z).astype(dt)
            return (z * dims.scale(path)).astype(dt)
        return walk(shapes, "")

    kw = {}
    if device is not None:
        from jax.sharding import SingleDeviceSharding
        kw["out_shardings"] = SingleDeviceSharding(device)
    return jax.jit(build, **kw)(jax_key(seed))
