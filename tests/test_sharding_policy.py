"""Sharding policy + HLO analyzer unit/property tests (host-side: these
never build the 512-device mesh; a tiny mesh stands in)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo_stats import analyze_hlo
from repro.configs import ARCHS, ASSIGNED, get_config
from repro.launch.sharding import (Policy, _cache_pspec, dp_spec,
                                   pool_pspec, serve_policy, span_pspec,
                                   train_policy)
from repro.models import zoo
from repro.models.spec import Spec, _walk


def _mesh():
    # 1 real device but arbitrary logical names: use Mesh of shape (1,1)
    return jax.make_mesh((1, 1), ("data", "model"))


class FakeMesh:
    """Shape-only stand-in so divisibility logic can be tested against
    the production (16,16) topology without 256 devices."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


PROD = FakeMesh({"data": 16, "model": 16})
PROD2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_divisibility_fallback():
    pol = train_policy(PROD)
    # 15 heads don't divide 16 -> replicated
    s = Spec((64, 15, 64), ("embed", "heads", None))
    assert pol.pspec(s, PROD) == P("data",)
    # 32 heads divide -> sharded
    s = Spec((64, 32, 64), ("embed", "heads", None))
    assert pol.pspec(s, PROD) == P("data", "model")


def test_axis_used_once_per_tensor():
    pol = Policy(rules={"a": ("model",), "b": ("model",)})
    s = Spec((32, 32), ("a", "b"))
    spec = pol.pspec(s, PROD)
    axes = [x for x in spec if x is not None]
    assert len(axes) == len(set(axes)) <= 1


def test_expert_weight_sharding():
    pol = train_policy(PROD)
    # mixtral: 8 experts can't shard over 16 -> expert_ff takes BOTH
    # axes (2D FSDP+TP); expert_in must never shard (a data-sharded
    # contraction dim all-reduces dispatch-sized fp32 tensors)
    s = Spec((8, 64, 2560), ("experts", "expert_in", "expert_ff"))
    assert pol.pspec(s, PROD) == P(None, None, ("model", "data"))
    # jamba: 16 experts shard over model (EP) -> expert_ff falls to data
    s = Spec((16, 64, 2560), ("experts", "expert_in", "expert_ff"))
    assert pol.pspec(s, PROD) == P("model", None, "data")


def test_serve_policy_fsdp_threshold():
    small = serve_policy(PROD, param_bytes=4 << 30)
    big = serve_policy(PROD, param_bytes=300 << 30)
    s = Spec((4096, 32, 128), ("embed", "heads", None))
    assert small.pspec(s, PROD) == P(None, "model")
    assert big.pspec(s, PROD) == P("data", "model")


def test_dp_spec():
    assert dp_spec(PROD, 256) == "data"
    assert dp_spec(PROD2, 256) == ("pod", "data")
    assert dp_spec(PROD, 1) is None
    assert dp_spec(PROD2, 2) is None      # 2 % 32 != 0 and 2 % 16 != 0


def test_cache_pspec_kv():
    # decode_32k-style: B=128 shards data, S shards model
    spec = _cache_pspec("k", (4, 128, 32768, 8, 128), PROD)
    assert spec == P(None, "data", "model", None, None)
    # long-context B=1: sequence takes everything
    spec = _cache_pspec("k", (4, 1, 524288, 8, 128), PROD)
    assert spec == P(None, None, ("data", "model"), None, None)


def test_cache_pspec_states():
    assert _cache_pspec("ssm", (4, 128, 8192, 16), PROD) == \
        P(None, "data", "model", None)
    assert _cache_pspec("state", (4, 128, 64, 64, 64), PROD) == \
        P(None, "data", "model", None, None)
    assert _cache_pspec("shift", (4, 128, 4096), PROD) == \
        P(None, "data", None)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_spec_tree_maps(arch):
    """Every parameter of every arch gets a legal PartitionSpec on both
    production meshes (dims divide, no axis reuse)."""
    api = zoo.build(get_config(arch))
    for mesh in (PROD, PROD2):
        pol = train_policy(mesh)

        def leaf(path, s):
            spec = pol.pspec(s, mesh)
            used = []
            for dim, ax in zip(s.shape, tuple(spec) + (None,) * 8):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                n = 1
                for a in axes:
                    n *= mesh.shape[a]
                    used.append(a)
                assert dim % n == 0, (arch, path, s.shape, spec)
            assert len(used) == len(set(used)), (arch, path, spec)
            return None

        _walk(api.specs, leaf)


# ---------------------------------------------------------------------
# Policy.pspec mechanics (preference order / divisibility / axis reuse)
# ---------------------------------------------------------------------

TP4 = FakeMesh({"data": 16, "model": 4})


def test_pspec_preference_order():
    # first pref that exists, divides, and is unused wins
    pol = Policy(rules={"x": (("model", "data"), ("model",), ("data",))})
    s = Spec((256,), ("x",))
    assert pol.pspec(s, PROD) == P(("model", "data"))
    # 16 divides model=16 but not model*data=256 -> second pref
    s = Spec((16,), ("x",))
    assert pol.pspec(s, PROD) == P("model")


def test_pspec_missing_axis_skipped():
    # "pod" absent on the single-pod mesh -> falls through to "model"
    pol = Policy(rules={"x": (("pod", "model"), ("model",))})
    s = Spec((32,), ("x",))
    assert pol.pspec(s, PROD) == P("model")
    assert pol.pspec(s, PROD2) == P(("pod", "model"))


def test_pspec_exhausted_prefs_replicate():
    pol = Policy(rules={"x": (("model",), ("data",))})
    s = Spec((15, 7), ("x", "x"))       # divides neither 16 axis
    assert pol.pspec(s, PROD) == P()    # trailing Nones popped


def test_pspec_tuple_pref_axis_reuse():
    # dim 0 takes "model"; dim 1's ("model","data") pref must be
    # rejected wholesale (partial reuse), falling through to ("data",)
    pol = Policy(rules={"a": ("model",),
                        "b": (("model", "data"), ("data",))})
    s = Spec((32, 256), ("a", "b"))
    assert pol.pspec(s, PROD) == P("model", "data")


def test_dp_spec_pod_fallback():
    # 16 % (pod*data)=32 != 0 but 16 % data=16 == 0 -> "data" alone
    assert dp_spec(PROD2, 16) == "data"


def test_serve_policy_big_fsdp_embed_rule():
    small = serve_policy(PROD, param_bytes=4 << 30)
    big = serve_policy(PROD, param_bytes=300 << 30)
    assert "embed" not in small.rules              # replicate when small
    assert big.rules["embed"] == ("data",)         # FSDP when big
    # expert FSDP engages with the same switch
    s = Spec((8, 64, 2560), ("experts", "expert_in", "expert_ff"))
    assert small.pspec(s, PROD) == P("model", None, None) or \
        small.pspec(s, PROD) == P(None, None, "model")
    assert big.pspec(s, PROD) == P(None, None, ("model", "data"))


# ---------------------------------------------------------------------
# serve-time paged-pool shardings + the GQA edge (DESIGN.md §13)
# ---------------------------------------------------------------------

def test_pool_pspec_head_wise_when_divisible():
    # leaves are [n_pages, KH, R, 128]; KH=8 divides tp=4 -> Megatron
    # head sharding
    assert pool_pspec((289, 8, 8, 128), TP4) == P(None, "model", None, None)


def test_pool_pspec_gqa_falls_back_to_slots():
    """The GQA edge: TP degree exceeds kv_heads -> heads must REPLICATE
    and a page's rows (its token slots) take the shard (an indivisible
    head spec would be a compile error, not a slow path)."""
    spec = pool_pspec((289, 1, 8, 128), TP4)
    assert spec == P(None, None, "model", None)
    assert spec[1] is None                         # heads replicated
    # KH=6 doesn't divide tp=4 either -> same fallback
    assert pool_pspec((289, 6, 8, 128), TP4) == P(None, None, "model", None)


def test_pool_pspec_page_wise_last_resort_and_replicate():
    # neither heads (1) nor rows (15) divide; pages (288) do
    assert pool_pspec((288, 1, 15, 128), TP4) == P("model", None, None, None)
    # nothing divides -> replicate rather than produce an illegal spec
    assert pool_pspec((289, 1, 15, 128), TP4) == P(None, None, None, None)
    # tp=1 or no "model" axis -> always replicate
    assert pool_pspec((289, 8, 8, 128), FakeMesh({"data": 4, "model": 1})) \
        == P(None, None, None, None)


def test_span_pspec_only_head_shard_carries_over():
    # head-sharded pools move per-shard DMA payloads (each chip ships
    # its own kv-head slice); slot/page-sharded pools replicate spans
    assert span_pspec((100, 8, 64), TP4) == P(None, "model", None)
    assert span_pspec((100, 1, 64), TP4) == P(None, None, None)
    assert span_pspec((3, 16, 8, 64), TP4) == P(None, None, "model", None)


def test_cache_pspec_head_preference_guarded():
    # decode-cell k/v now prefer head-wise TP when KH divides
    assert _cache_pspec("k", (4, 128, 32768, 8, 128), TP4) == \
        P(None, "data", None, "model", None)
    # GQA edge (KH=2, tp=4): heads replicate, sequence takes "model" —
    # the pre-SPMD behavior, byte-identical
    assert _cache_pspec("k", (4, 128, 32768, 2, 128), TP4) == \
        P(None, "data", "model", None, None)
    # production (16,16): KH=8 % 16 != 0 -> unchanged from before
    assert _cache_pspec("k", (4, 128, 32768, 8, 128), PROD) == \
        P(None, "data", "model", None, None)


def test_hlo_analyzer_counts_nested_loops():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out
    xs = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    ws = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    txt = jax.jit(f).lower(xs, ws).compile().as_text()
    s = analyze_hlo(txt)
    assert s.dot_flops == 2 * 8 * 16 * 16 * 15
    assert s.n_while == 2
    assert s.dot_bytes > 0
