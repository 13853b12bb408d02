"""Plain reference of a dense pre-norm decoder (Llama / Mistral /
SmolLM): RMSNorm, grouped-query attention with rotary positions on the
two halves of each head (the ``rotate_half`` form of the published
code), SwiGLU MLP, a final RMSNorm and a tied or separate head.

Written from the architecture's published description in plain
``jax.numpy``; it imports nothing of the program. One causal forward
over a whole sequence, layer by layer under ``lax.scan`` (each layer's
weights upcast on their own) and attention in blocks of queries, so a
7B-class layer stack and 4096 positions fit beside nothing else on one
16 GB chip. It computes in float32 at "highest" matmul precision.

``quant`` gives the controls: every projection and the head quantised
per output channel, to int8 (``"int8"``) or to float8 e4m3
(``"fp8"``), and computed in bfloat16: the cheaper precisions a later
change might be tempted to serve in.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 512

# axes each of one layer's weights contracts over
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
             "wg": (0,), "wu": (0,), "wd": (0,)}


def _quantised(w, axes, quant):
    """Symmetric per-output-channel quantisation: scale so that the
    largest magnitude over the contracted ``axes`` meets the format's
    largest value (127 for int8, 448 for e4m3), round to the format,
    and return the dequantised bfloat16 weight."""
    wf = w.astype(jnp.float32)
    top = 127.0 if quant == "int8" else 448.0
    s = jnp.max(jnp.abs(wf), axis=axes, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        q = jnp.clip(jnp.round(wf / s), -127, 127)
    else:
        q = (wf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * s).astype(jnp.bfloat16)


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x [S, H, D]; rotate the pair (x[i], x[i + D/2]) by
    pos * theta**(-2i/D)."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2].astype(jnp.float32), x[..., D // 2:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, dt):
    """Causal attention, q [S, H, D], k/v [S, KH, D]; query head h reads
    kv head h // (H / KH). Queries in blocks of Q_BLOCK."""
    S, H, D = q.shape
    KH = k.shape[1]
    G = H // KH
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, KH, G, D)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, k,
                       preferred_element_type=jnp.float32) * D ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("hgqk,khd->qhgd", p, v,
                          preferred_element_type=jnp.float32).astype(dt)

    o = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    return o.reshape(S, H, D)


def forward_logits(w, tokens, positions, *, dims, quant=None):
    """Logits (float32, [len(positions), vocab]) of a causal forward
    over ``tokens`` [S] at each of ``positions``: the distribution of
    the token that follows position p."""
    dt = jnp.float32 if quant is None else jnp.bfloat16
    st = w["stack"]["p0"]
    layers = {"n1": st["norm1"], "n2": st["norm2"], **st["attn"],
              **st["ffn"]}
    if quant not in (None, "int8", "fp8"):
        raise ValueError(f"unknown quant {quant!r}")
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = w["embed"]["tok"][tokens].astype(dt)

    def layer(x, p):
        if quant:       # one layer at a time: no quantised copy of all
            p = {k: (_quantised(v, _CONTRACT[k], quant)
                     if k in _CONTRACT else v) for k, v in p.items()}
        p = jax.tree.map(lambda a: a.astype(dt), p)
        h = _rms(x, p["n1"], dims.eps)
        q = _rope(jnp.einsum("sd,dhk->shk", h, p["wq"]), pos,
                  dims.rope_theta)
        k = _rope(jnp.einsum("sd,dhk->shk", h, p["wk"]), pos,
                  dims.rope_theta)
        v = jnp.einsum("sd,dhk->shk", h, p["wv"])
        x = x + jnp.einsum("shk,hkd->sd", _attention(q, k, v, dt), p["wo"])
        h = _rms(x, p["n2"], dims.eps)
        g = jax.nn.silu(jnp.einsum("sd,df->sf", h, p["wg"]).astype(
            jnp.float32))
        u = jnp.einsum("sd,df->sf", h, p["wu"]).astype(jnp.float32)
        return x + jnp.einsum("sf,fd->sd", (g * u).astype(dt), p["wd"]), None

    x, _ = jax.lax.scan(layer, x, layers)
    h = _rms(x[positions], w["embed"]["final_norm"].astype(dt), dims.eps)
    if dims.tied:
        head = w["embed"]["tok"]
        head = (_quantised(head, (1,), quant) if quant else head).astype(dt)
        return jnp.einsum("pd,vd->pv", h, head,
                          preferred_element_type=jnp.float32)
    head = w["embed"]["head"]
    head = (_quantised(head, (0,), quant) if quant else head).astype(dt)
    return jnp.einsum("pd,dv->pv", h, head,
                      preferred_element_type=jnp.float32)


def compiled(dims, quant=None):
    """The jitted forward: float32 at "highest" precision, or the
    control in bfloat16 at the default precision."""
    fn = jax.jit(partial(forward_logits, dims=dims, quant=quant))
    if quant is not None:
        return fn

    def run(w, tokens, positions):
        with jax.default_matmul_precision("highest"):
            return fn(w, tokens, positions)
    return run
