"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (deliverable c's per-kernel allclose)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention, lse_merge
from repro.kernels.flash_attention import flash_attention
from repro.kernels.prefix_attention import prefix_attention
from repro.kernels import ops

K0 = jax.random.PRNGKey(0)


def rnd(key, *s, dt=jnp.float32):
    return jax.random.normal(key, s, dt)


FLASH_CASES = [
    # B, H, KH, Sq, Skv, D, causal, window
    (2, 4, 2, 128, 128, 64, True, 0),
    (1, 8, 8, 96, 96, 128, True, 0),      # MHA
    (2, 4, 1, 64, 192, 64, False, 0),     # cross-shape, MQA
    (1, 6, 2, 256, 256, 64, True, 64),    # sliding window
    (2, 2, 2, 40, 72, 32, True, 0),       # non-block-multiple
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention(case):
    B, H, KH, Sq, Skv, D, causal, win = case
    k1, k2, k3 = jax.random.split(K0, 3)
    q = rnd(k1, B, H, Sq, D)
    k = rnd(k2, B, KH, Skv, D)
    v = rnd(k3, B, KH, Skv, D)
    out = flash_attention(q, k, v, causal=causal, window=win,
                          block_q=64, block_k=64, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dt):
    k1, k2, k3 = jax.random.split(K0, 3)
    q = rnd(k1, 1, 4, 64, 64).astype(dt)
    k = rnd(k2, 1, 2, 64, 64).astype(dt)
    v = rnd(k3, 1, 2, 64, 64).astype(dt)
    out = flash_attention(q, k, v, interpret=True, block_q=32, block_k=32)
    exp = ref.flash_attention_ref(q, k, v)
    atol = 3e-5 if dt == jnp.float32 else 3e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               exp.astype(np.float32), atol=atol, rtol=0.05)


DEC_CASES = [(4, 8, 2, 256, 64, 4), (2, 4, 4, 100, 128, 3),
             (1, 16, 8, 512, 64, 8), (3, 6, 6, 64, 32, 1)]


@pytest.mark.parametrize("case", DEC_CASES)
def test_decode_attention(case):
    B, H, KH, S, D, ns = case
    k1, k2, k3 = jax.random.split(K0, 3)
    q = rnd(k1, B, H, D)
    k = rnd(k2, B, KH, S, D)
    v = rnd(k3, B, KH, S, D)
    lens = jnp.asarray(np.random.default_rng(0).integers(1, S + 1, B),
                       jnp.int32)
    out = decode_attention(q, k, v, lens, n_splits=ns, interpret=True)
    exp = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


PRE_CASES = [(4, 8, 2, 256, 32, 64), (2, 4, 4, 128, 16, 128),
             (1, 8, 1, 512, 8, 64)]


@pytest.mark.parametrize("case", PRE_CASES)
def test_prefix_attention(case):
    B, H, KH, Sp, Ss, D = case
    ks_ = jax.random.split(K0, 5)
    q = rnd(ks_[0], B, H, D)
    kp, vp = rnd(ks_[1], KH, Sp, D), rnd(ks_[2], KH, Sp, D)
    ks, vs = rnd(ks_[3], B, KH, Ss, D), rnd(ks_[4], B, KH, Ss, D)
    lens = jnp.asarray(np.random.default_rng(1).integers(1, Ss + 1, B),
                       jnp.int32)
    out = prefix_attention(q, kp, vp, ks, vs, lens, interpret=True)
    exp = ref.prefix_attention_ref(q, kp, vp, ks, vs, lens)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


def test_lse_merge_degenerate():
    """Merge with one side fully masked (-inf m) stays finite."""
    acc = jnp.stack([jnp.zeros((1, 1, 2, 4)), jnp.ones((1, 1, 2, 4))], 2)
    m = jnp.stack([jnp.full((1, 1, 2, 1), -jnp.inf),
                   jnp.zeros((1, 1, 2, 1))], 2)
    l = jnp.stack([jnp.zeros((1, 1, 2, 1)), jnp.ones((1, 1, 2, 1))], 2)
    out = lse_merge(acc, m, l)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out, jnp.ones((1, 1, 2, 4)), atol=1e-6)


def test_ops_layout_wrappers():
    """ops.py adapts model layout [B,S,H,D] <-> kernel layout."""
    k1, k2, k3 = jax.random.split(K0, 3)
    q = rnd(k1, 2, 32, 4, 16)
    k = rnd(k2, 2, 32, 2, 16)
    v = rnd(k3, 2, 32, 2, 16)
    out = ops.flash_attention(q, k, v, block_q=16, block_k=16,
                              interpret=True)
    exp = ref.flash_attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


PAGED_CASES = [(3, 8, 2, 16, 4, 32, 64), (2, 4, 4, 8, 6, 24, 32),
               (1, 16, 8, 32, 3, 16, 128)]


def paged_pool(key, n_pages, page, KH, D):
    """Random KV pages token-major [n_pages, page, KH, D] (what the
    oracles read), and the same values as a pool leaf in the layout the
    kernel reads (models.attention.tokens_to_pages)."""
    from repro.models.attention import tokens_to_pages
    tok = rnd(key, n_pages, page, KH, D)
    return tok, tokens_to_pages(tok.reshape(n_pages * page, KH, D), page)


def dense_oracle(q, k_tok, v_tok, pt, lens):
    """Decode attention over each lane's table rows, gathered from the
    token-major pages."""
    B = q.shape[0]
    n, page, KH, D = k_tok.shape
    P = pt.shape[1]
    dense_k = jnp.stack([k_tok[pt[b]].reshape(page * P, KH, D)
                         for b in range(B)])
    dense_v = jnp.stack([v_tok[pt[b]].reshape(page * P, KH, D)
                         for b in range(B)])
    return ref.decode_attention_ref(q, dense_k.transpose(0, 2, 1, 3),
                                    dense_v.transpose(0, 2, 1, 3),
                                    jnp.asarray(lens))


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_attention(case):
    """Page-table-driven decode attention == dense-gathered oracle."""
    from repro.kernels.paged_attention import paged_decode_attention
    B, H, KH, page, P, n_pages, D = case
    ks = jax.random.split(K0, 3)
    k_tok, k_pages = paged_pool(ks[0], n_pages, page, KH, D)
    v_tok, v_pages = paged_pool(ks[1], n_pages, page, KH, D)
    q = rnd(ks[2], B, H, D)
    rng = np.random.default_rng(case[0])
    pt = np.stack([rng.choice(n_pages, P, replace=False)
                   for _ in range(B)])
    lens = rng.integers(1, page * P + 1, B)
    out = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(pt),
                                 jnp.asarray(lens), interpret=True)
    exp = dense_oracle(q, k_tok, v_tok, pt, lens)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


# ragged lens: shorter than one page, mid-page (partial last page),
# page-exact boundary, and the full table
PAGED_EDGE_LENS = [[3, 16, 21, 64], [1, 8, 48, 63], [16, 32, 5, 17]]


@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("lens", PAGED_EDGE_LENS)
def test_paged_decode_attention_ragged_lens(lens, D):
    """Per-request lengths hitting every page-boundary edge: len <
    page_size, partial last page, exact page multiple, full table; an
    odd length ends inside a packed row at D = 64."""
    from repro.kernels.paged_attention import paged_decode_attention
    B, H, KH, page, P, n_pages = len(lens), 8, 2, 16, 4, 24
    ks = jax.random.split(K0, 3)
    k_tok, k_pages = paged_pool(ks[0], n_pages, page, KH, D)
    v_tok, v_pages = paged_pool(ks[1], n_pages, page, KH, D)
    q = rnd(ks[2], B, H, D)
    rng = np.random.default_rng(7)
    pt = np.stack([rng.choice(n_pages, P, replace=False) for _ in range(B)])
    out = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(pt),
                                 jnp.asarray(lens), interpret=True)
    exp = dense_oracle(q, k_tok, v_tok, pt, lens)
    np.testing.assert_allclose(out, exp, atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("D", [64, 128])
def test_paged_gather_reference_matches_kernel(D):
    """models/attention.paged_attention 'gather' impl (the CPU engine
    path) == the Pallas kernel (interpret) on the same inputs."""
    from repro.kernels.paged_attention import paged_decode_attention
    from repro.models.attention import paged_attention
    B, H, KH, page, P, n_pages = 3, 8, 2, 16, 4, 24
    ks = jax.random.split(K0, 3)
    _, k_pages = paged_pool(ks[0], n_pages, page, KH, D)
    _, v_pages = paged_pool(ks[1], n_pages, page, KH, D)
    q = rnd(ks[2], B, H, D)
    rng = np.random.default_rng(11)
    pt = jnp.asarray(np.stack([rng.choice(n_pages, P, replace=False)
                               for _ in range(B)]))
    lens = jnp.asarray([5, 31, 64])
    got = paged_attention(q, k_pages, v_pages, pt, lens, impl="gather")
    exp = paged_decode_attention(q, k_pages, v_pages, pt, lens,
                                 interpret=True)
    np.testing.assert_allclose(got, exp, atol=3e-5, rtol=1e-4)
