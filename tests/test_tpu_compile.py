"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the tiling, kernels over their fast-memory budget. These tests compile
the paged-decode kernel at smollm-360m widths, and a full-width
2-layer serving step with that kernel in it, for one chip of a described
v5e:2x2, and check that the compiled program holds the kernel
(``tpu_custom_call``). The pool guard checks that the full-width decode
and mixed step programs update the KV page pool in place: no op of a
pool leaf's shape but the donated leaf's in-place updates, and
temporaries that do not grow with the pool. Nothing runs, so nothing
here says anything about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import attention, zoo
from repro.models.attention import pool_leaf_shape

MAX_CONTEXT = 4096          # the chip smoke's context bound
POOL_TOKENS = 65536         # ... and its per-chip KV pool


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip writes a cache entry that cannot
    # be read back without the chip: keep the persistent cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("page_size", [128, 16])
def test_paged_decode_kernel_compiles(one_chip, page_size):
    cfg = get_config("smollm-360m")
    n_pages = POOL_TOKENS // page_size + 17
    pool = _shape(one_chip, pool_leaf_shape(n_pages, page_size,
                                            cfg.n_kv_heads, cfg.head_dim),
                  jnp.bfloat16)
    compiled = jax.jit(paged_decode_attention).lower(
        _shape(one_chip, (8, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        pool, pool,
        _shape(one_chip, (8, MAX_CONTEXT // page_size), jnp.int32),
        _shape(one_chip, (8,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_two_layer_mixed_step_compiles_with_kernel(one_chip, monkeypatch):
    # this process's backend is the CPU, so 'auto' would pick the
    # gather path; the chip's backend picks the kernel
    monkeypatch.setattr(attention, "auto_paged_impl", lambda: "pallas")
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2)
    api = zoo.build(cfg)
    ps = 128
    n_pages, P = POOL_TOKENS // ps + 17, MAX_CONTEXT // ps
    lanes, chunk = 2, 128

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                            tree)

    i32 = jnp.int32
    batch = {"chunk_tokens": _shape(one_chip, (lanes, chunk), i32),
             "chunk_start": _shape(one_chip, (lanes,), i32),
             "chunk_len": _shape(one_chip, (lanes,), i32),
             "chunk_page_table": _shape(one_chip, (lanes, P), i32),
             "dec_tokens": _shape(one_chip, (lanes,), i32),
             "dec_pos": _shape(one_chip, (lanes,), i32),
             "dec_page_table": _shape(one_chip, (lanes, P), i32)}
    step = jax.jit(api.mixed_paged, donate_argnums=(1,))
    compiled = step.lower(place(api.abstract()),
                          place(api.paged_cache_specs(n_pages, ps)),
                          batch).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2   # per layer


# ---------------------------------------------------------------------
# pool guard: the step programs hold the pool in place
# ---------------------------------------------------------------------

# ops that may produce a value of a pool leaf's shape: the donated leaf
# itself, views of it, and its in-place updates
IN_PLACE = {"parameter", "get-tuple-element", "bitcast", "scatter",
            "dynamic-update-slice"}
INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} "
                   r"([\w-]+)\((.*)$")


def _computations(hlo: str):
    """{computation name: [(dtype, dims, opcode, rest of line)]}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) ", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
            continue
        m = INSTR.match(line)
        if m and name is not None:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            comps[name].append((m.group(1), dims, m.group(3), m.group(4),
                                line.lstrip().startswith("ROOT")))
    return comps


def _pool_sized_ops(hlo: str, leaf):
    """Ops whose result has a pool leaf's dims (in any order) and is not
    an in-place update of it; a fusion counts as in place when its root
    is a scatter or a dynamic-update-slice."""
    comps = _computations(hlo)
    roots = {n: next((op for *_, op, _, root in ins if root), None)
             for n, ins in comps.items()}
    want = sorted(leaf)
    bad = []
    for name, ins in comps.items():
        for _dt, dims, op, rest, _root in ins:
            if sorted(dims) != want or op in IN_PLACE:
                continue
            called = re.search(r"calls=%([\w.\-]+)", rest)
            if op == "fusion" and called and roots.get(called.group(1)) in (
                    "scatter", "dynamic-update-slice"):
                continue
            bad.append(f"{name}: {op} {dims}")
    # a loop carrying a leaf updates it a token at a time (what XLA
    # makes of a scatter it cannot apply as one op)
    carried = re.compile(r"\[" + r",".join(map(str, leaf)) + r"\]")
    bad += [line.strip()[:80] for line in hlo.splitlines()
            if " while(" in line and carried.search(line.split("=")[1])]
    return bad


STEP_CASES = {"smollm-360m": (131072, 262144),    # KH 5, D 64 (packed)
              "mistral-7b": (32768, 65536)}       # KH 8, D 128


def _compile_step(one_chip, api, kind, n_pages):
    ps, lanes, chunk, P = 128, 4, 512, MAX_CONTEXT // 128
    i32 = jnp.int32

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                            tree)

    if kind == "decode":
        fn = api.decode_paged
        batch = {"tokens": _shape(one_chip, (lanes,), i32),
                 "pos": _shape(one_chip, (lanes,), i32),
                 "page_table": _shape(one_chip, (lanes, P), i32)}
    else:
        fn = api.mixed_paged
        batch = {"chunk_tokens": _shape(one_chip, (lanes, chunk), i32),
                 "chunk_start": _shape(one_chip, (lanes,), i32),
                 "chunk_len": _shape(one_chip, (lanes,), i32),
                 "chunk_page_table": _shape(one_chip, (lanes, P), i32),
                 "dec_tokens": _shape(one_chip, (lanes,), i32),
                 "dec_pos": _shape(one_chip, (lanes,), i32),
                 "dec_page_table": _shape(one_chip, (lanes, P), i32)}
    pool = api.paged_cache_specs(n_pages, ps)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        place(api.abstract()), place(pool), batch).compile()
    return compiled, jax.tree.leaves(pool)


@pytest.mark.parametrize("kind", ["decode", "mixed"])
@pytest.mark.parametrize("arch", sorted(STEP_CASES))
def test_step_updates_pool_in_place(one_chip, monkeypatch, arch, kind):
    """The 2-layer decode and mixed step programs at full width, with
    the kernel, at the benchmark's pool and at twice it: every pool leaf
    is donated and aliased, no op but the leaf's in-place updates has a
    leaf's shape (no relayout copy, no transpose for the kernel), and
    the temporaries are the same at both pool sizes (none holds a
    pool-sized buffer)."""
    monkeypatch.setattr(attention, "auto_paged_impl", lambda: "pallas")
    cfg = dataclasses.replace(get_config(arch), n_layers=2,
                              sliding_window=0)
    api = zoo.build(cfg)
    temps = []
    for tokens in STEP_CASES[arch]:
        n_pages = tokens // 128 + 9
        compiled, leaves = _compile_step(one_chip, api, kind, n_pages)
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo
        leaf = leaves[0].shape
        assert _pool_sized_ops(hlo, leaf) == []
        mem = compiled.memory_analysis()
        leaf_bytes = math.prod(leaf) * leaves[0].dtype.itemsize
        assert mem.alias_size_in_bytes >= len(leaves) * leaf_bytes
        temps.append((mem.temp_size_in_bytes, leaf_bytes))
    (small, leaf_bytes), (large, _) = temps
    assert abs(large - small) < leaf_bytes // 100, temps
