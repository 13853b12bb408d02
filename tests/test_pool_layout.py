"""The KV page pool's layout (DESIGN.md §2): each leaf is [n_pages, KH,
R, 128], token t of a page at row (t * D) // 128, lane (t * D) % 128 of
its head's block. Tokens written through models.attention's helpers
(decode writes, prefill chunks, the host-tier restore) read back
unchanged through the gather helpers and the demote path, for a head
dim that packs two tokens a row (64) and one that fills it (128)."""

import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import zoo
from repro.models.attention import (gather_tokens, pages_to_tokens,
                                    pool_leaf_shape, tokens_to_pages,
                                    write_runs)
from repro.serving.engine import Engine, EngineConfig

PS, KH, N_PAGES = 128, 2, 8
DIMS = [64, 128]


def _tokens(D, seed=0):
    """Random token-major pages [N_PAGES, PS, KH, D]."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((N_PAGES, PS, KH, D)).astype(np.float32)


def _leaf(tok):
    return tokens_to_pages(jnp.asarray(tok).reshape(-1, KH, tok.shape[-1]),
                           PS)


def _write_reference(tok, table, start, length, vals):
    """What ``write_runs`` must leave: tok with lane b's first length[b]
    tokens at positions start[b] + t of its table row."""
    out = tok.copy()
    for b in range(len(start)):
        for t in range(length[b]):
            pos = start[b] + t
            out[table[b, pos // PS], pos % PS] = vals[b, t]
    return out


def _assert_pool(leaf, expect, D):
    """Every page but the scratch page 0 holds ``expect``."""
    got = np.asarray(pages_to_tokens(leaf, D)).reshape(expect.shape)
    np.testing.assert_array_equal(got[1:], expect[1:])


@pytest.mark.parametrize("D", DIMS)
def test_leaf_shape_and_token_addresses(D):
    tok = _tokens(D)
    leaf = np.asarray(_leaf(tok))
    assert leaf.shape == pool_leaf_shape(N_PAGES, PS, KH, D) \
        == (N_PAGES, KH, PS * D // 128, 128)
    for t in (0, 1, 2, 63, 126, 127):
        row, lane = t * D // 128, t * D % 128
        np.testing.assert_array_equal(leaf[3, 1, row, lane:lane + D],
                                      tok[3, t, 1])
    np.testing.assert_array_equal(
        np.asarray(pages_to_tokens(leaf, D)), tok.reshape(-1, KH, D))
    # the numpy form (the demote drain) reads the same tokens
    np.testing.assert_array_equal(pages_to_tokens(leaf[2:5], D),
                                  tok[2:5].reshape(-1, KH, D))


@pytest.mark.parametrize("D", DIMS)
def test_gather_tokens_reads_table_rows(D):
    tok = _tokens(D, 1)
    table = np.array([[5, 2, 7], [1, 1, 6]], np.int32)
    got = gather_tokens(_leaf(tok), jnp.asarray(table), D)
    np.testing.assert_array_equal(
        np.asarray(got), tok[table].reshape(2, 3 * PS, KH, D))


@pytest.mark.parametrize("D", DIMS)
def test_chunk_from_odd_position_across_a_page_boundary(D):
    """A chunk of 60 real tokens (64 with padding) from position 101:
    it starts inside a packed row at D = 64 and runs from the table's
    first page into its second; the padding writes nothing, and a
    padding lane (length 0, all-scratch row) writes only page 0."""
    tok = _tokens(D, 2)
    C = 64
    table = np.array([[3, 5, 1, 0], [0, 0, 0, 0]], np.int32)
    start, length = np.array([101, 0]), np.array([60, 0])
    vals = np.random.default_rng(3).standard_normal(
        (2, C, KH, D)).astype(np.float32)
    leaf = write_runs(_leaf(tok), jnp.asarray(table), jnp.asarray(start),
                      jnp.asarray(length), jnp.asarray(vals))
    expect = _write_reference(tok, table, start, length, vals)
    assert not np.array_equal(expect[5], tok[5])        # crossed over
    _assert_pool(leaf, expect, D)


@pytest.mark.parametrize("D", DIMS)
def test_decode_writes_at_page_edges(D):
    """One token per lane at in-page offsets 0, 1, 126 and 127, each on
    its own page, with a padding lane (all-scratch row, position 0)."""
    tok = _tokens(D, 4)
    pos = np.array([0, 129, 254, 383, 0])
    table = np.array([[2, 0, 0, 0], [6, 4, 0, 0], [1, 7, 0, 0],
                      [6, 1, 3, 5], [0, 0, 0, 0]], np.int32)
    assert list(pos % PS) == [0, 1, 126, 127, 0]
    vals = np.random.default_rng(5).standard_normal(
        (5, 1, KH, D)).astype(np.float32)
    one = np.ones(5, np.int32)
    leaf = write_runs(_leaf(tok), jnp.asarray(table), jnp.asarray(pos),
                      jnp.asarray(one), jnp.asarray(vals))
    _assert_pool(leaf, _write_reference(tok, table[:4], pos[:4], one[:4],
                                        vals[:4]), D)


@pytest.fixture(scope="module", params=DIMS)
def host_tier_engine(request):
    """A paged engine with a host tier at head dim D, one kv head."""
    cfg = dataclasses.replace(reduced(ARCHS["smollm-360m"]), n_layers=1,
                              dtype="float32", head_dim=request.param)
    params = zoo.build(cfg).init(jax.random.PRNGKey(0))
    ec = EngineConfig(max_context=512, chunk_size=64, max_batch_tokens=256,
                      max_batch_requests=2, capacity_tokens=1024,
                      page_size=PS, paged=True, host_capacity_tokens=4096)
    return Engine(cfg, params, ec)


def _leaves(eng, table_pages, D):
    """The engine's pool, token-major over ``table_pages``, per leaf."""
    gathered, n = eng.gather_pages_device(table_pages)
    return [np.asarray(pages_to_tokens(np.asarray(a)[:n], D))
            for a in jax.tree.leaves(gathered)]


def test_restore_then_demote_round_trip(host_tier_engine):
    """The host-tier restore writes tokens [101, 300) into a table's
    pages (from mid-row, across two page boundaries) and leaves the
    tokens [0, 101) an earlier restore wrote as they were; demoting the
    span reads the same tokens back, token-major, into the host
    store."""
    eng = host_tier_engine
    D = eng.model_cfg.head_dim
    kh = eng.model_cfg.n_kv_heads
    rid = ("req", "layout")
    eng.pool.create(rid)
    eng.pool.append(rid, 300)
    table = eng.pool.tables[rid]
    lo, hi = 101, 300
    rng = np.random.default_rng(6)

    def restore(a, b):
        data = jax.tree.map(lambda _: rng.standard_normal(
            (b - a, kh, D)).astype(np.float32), eng.pages)
        pidx, sidx = Engine._token_page_slots(table, PS, a, b)
        eng._scatter_staged([(pidx, sidx, data)])
        return data

    head = restore(0, lo)
    data = restore(lo, hi)
    after = _leaves(eng, table.pages, D)
    for a, h, d in zip(after, jax.tree.leaves(head),
                       jax.tree.leaves(data)):
        np.testing.assert_array_equal(a[:lo], h)
        np.testing.assert_array_equal(a[lo:hi], d)
    # demote the span as a radix node covering [lo, hi) would be
    node = SimpleNamespace(path_key=("layout",), node_id=7,
                           tokens=tuple(range(hi - lo)),
                           depth_tokens=lambda: hi)
    eng.pool.fork(rid, ("node", node.path_key), hi)
    ht = eng.scheduler.host_tier
    assert ht.demote_many([node]) == {node.path_key: hi - lo}
    ht.drain()
    entry = eng.host_store.get(node.path_key)
    for got, d in zip(jax.tree.leaves(entry.kv), jax.tree.leaves(data)):
        np.testing.assert_array_equal(got, d)
    eng.pool.release(rid)
