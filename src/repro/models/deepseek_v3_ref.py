"""Plain reference of DeepSeek-V3's layer kinds in `jax.numpy`: the layers
`LLMWorkload.kind_ops` counts, checked against it at small widths.

One dense block (MLA + SwiGLU), one MoE block (MLA + sigmoid router +
top-k routed SwiGLU experts + shared experts) and the MTP block (eh_proj
over the normed hidden state and the next token's embedding, then an MoE
block). MLA runs in its published form for a whole sequence (`forward`,
`prefill`: `kv_b` expands the latent into per-head k and v) and in the
absorbed form for one new token (`decode`: q_nope folded into the latent,
scores over the cached latent and rope key, attn-v over the latent, then
the per-head up-projection). The cache holds kv_lora_rank +
qk_rope_head_dim values a token a layer.

Float32 throughout; call under `jax.default_matmul_precision("highest")`.
Routed experts are gathered per token (`w[idx]`), so their matmuls are the
top-k ones, not a dense dispatch. The configuration is an `LLMWorkload`
with a table of layer kinds (its MLA, expert and MTP fields).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, embed_init, rmsnorm, rope

ROPE_THETA = 10000.0


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _mla_params(key, wl) -> Dict:
    D, H = wl.d_model, wl.n_heads
    c, r = wl.kv_lora_rank, wl.qk_rope_head_dim
    nope, v, ql = wl.qk_nope_head_dim, wl.v_head_dim, wl.q_lora_rank
    ks = jax.random.split(key, 5)
    return {"q_a": dense_init(ks[0], (D, ql)), "q_norm": jnp.zeros((ql,)),
            "q_b": dense_init(ks[1], (ql, H, nope + r), scale=ql ** -0.5),
            "kv_a": dense_init(ks[2], (D, c + r)),
            "kv_norm": jnp.zeros((c,)),
            "kv_b": dense_init(ks[3], (c, H, nope + v), scale=c ** -0.5),
            "o": dense_init(ks[4], (H, v, D), scale=(H * v) ** -0.5)}


def _swiglu_params(key, d_in: int, width: int, stack: Tuple = ()) -> Dict:
    k1, k2 = jax.random.split(key)
    return {"w_in": dense_init(k1, (*stack, d_in, 2 * width)),
            "w_out": dense_init(k2, (*stack, width, d_in))}


def _block_params(key, wl, moe: bool) -> Dict:
    D = wl.d_model
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((D,)), "ln2": jnp.zeros((D,)),
         "attn": _mla_params(k1, wl)}
    if not moe:
        p["mlp"] = _swiglu_params(k2, D, wl.d_ff)
        return p
    p["router"] = dense_init(k2, (D, wl.moe_experts))
    p["experts"] = _swiglu_params(k3, D, wl.d_ff_expert, (wl.moe_experts,))
    p["shared"] = _swiglu_params(k4, D, wl.d_ff_expert * wl.moe_shared)
    return p


def init_params(key, wl) -> Dict:
    """A dense block, an MoE block, the MTP block, embedding and head."""
    D = wl.d_model
    ks = jax.random.split(key, 6)
    return {"embed": embed_init(ks[0], (wl.vocab, D)),
            "dense": _block_params(ks[1], wl, moe=False),
            "moe": _block_params(ks[2], wl, moe=True),
            "mtp": dict(_block_params(ks[3], wl, moe=True),
                        eh_proj=dense_init(ks[4], (2 * D, D)),
                        norm_h=jnp.zeros((D,)), norm_e=jnp.zeros((D,))),
            "final_ln": jnp.zeros((D,)),
            "head": dense_init(ks[5], (D, wl.vocab))}


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------


def _queries(p, wl, x, pos):
    """(q_nope, q_rope) of x (B, S, D) at positions pos (B, S)."""
    nope = wl.qk_nope_head_dim
    q = jnp.einsum("bsl,lhd->bshd", rmsnorm(x @ p["q_a"], p["q_norm"]),
                   p["q_b"])
    return q[..., :nope], rope(q[..., nope:], pos, ROPE_THETA)


def _latent(p, wl, x, pos):
    """The cache entries of x: the normed latent (B, S, c) and the rope key
    (B, S, r), shared by every head."""
    c = wl.kv_lora_rank
    kv = x @ p["kv_a"]
    k_rope = rope(kv[..., None, c:], pos, ROPE_THETA)[:, :, 0]
    return rmsnorm(kv[..., :c], p["kv_norm"]), k_rope


def mla(p, wl, x, pos):
    """Causal MLA over a whole sequence, published form. Returns the output
    and the cache entries (latent, rope key)."""
    nope = wl.qk_nope_head_dim
    q_nope, q_rope = _queries(p, wl, x, pos)
    c_kv, k_rope = _latent(p, wl, x, pos)
    kv = jnp.einsum("bsc,chd->bshd", c_kv, p["kv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  k_nope.shape[:3] + k_rope.shape[-1:])],
        axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1] * 1.0)
    S = x.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bshv,hvd->bsd", o, p["o"]), (c_kv, k_rope)


def mla_decode(p, wl, x, cache, pos, keep_rope: bool = True):
    """One new token a sequence (x (B, 1, D) at positions pos (B,)),
    absorbed form, against a cache of (latent (B, T, c), rope key
    (B, T, r)) of length T. `keep_rope=False` is the control: the rope
    key left out of the cache."""
    nope = wl.qk_nope_head_dim
    q_nope, q_rope = _queries(p, wl, x, pos[:, None])
    c_new, r_new = _latent(p, wl, x, pos[:, None])
    if not keep_rope:
        r_new = jnp.zeros_like(r_new)
    b = jnp.arange(x.shape[0])
    c_kv = cache[0].at[b, pos].set(c_new[:, 0])
    k_rope = cache[1].at[b, pos].set(r_new[:, 0])
    w_uk, w_uv = p["kv_b"][..., :nope], p["kv_b"][..., nope:]
    q_lat = jnp.einsum("bqhn,chn->bqhc", q_nope, w_uk)
    s = (jnp.einsum("bqhc,btc->bhqt", q_lat, c_kv)
         + jnp.einsum("bqhr,btr->bhqt", q_rope, k_rope))
    s = s / jnp.sqrt((nope + wl.qk_rope_head_dim) * 1.0)
    T = c_kv.shape[1]
    live = jnp.arange(T)[None, :] <= pos[:, None]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    o_lat = jnp.einsum("bhqt,btc->bqhc", jax.nn.softmax(s, axis=-1), c_kv)
    o = jnp.einsum("bqhc,chv->bqhv", o_lat, w_uv)
    return jnp.einsum("bshv,hvd->bsd", o, p["o"]), (c_kv, k_rope)


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------


def _swiglu(h):
    half = h.shape[-1] // 2
    return jax.nn.silu(h[..., :half]) * h[..., half:]


def swiglu(p, x):
    return _swiglu(x @ p["w_in"]) @ p["w_out"]


def moe(p, wl, x):
    """Sigmoid router, top-k routed experts gathered per token, their
    gates normalized over the k; plus the shared experts."""
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(xt @ p["router"])
    gate, idx = jax.lax.top_k(scores, wl.moe_topk)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    h = jnp.einsum("md,mkdf->mkf", xt, p["experts"]["w_in"][idx])
    y = jnp.einsum("mkf,mkfd->mkd", _swiglu(h), p["experts"]["w_out"][idx])
    y = jnp.sum(y * gate[..., None], axis=1) + swiglu(p["shared"], xt)
    return y.reshape(shape)


# ---------------------------------------------------------------------------
# blocks and the model
# ---------------------------------------------------------------------------


def _ffn(p, wl, x):
    return swiglu(p["mlp"], x) if "mlp" in p else moe(p, wl, x)


def block(p, wl, x, pos):
    """A dense or MoE block over a whole sequence; returns the output and
    the block's cache entries."""
    a, cache = mla(p["attn"], wl, rmsnorm(x, p["ln1"]), pos)
    x = x + a
    return x + _ffn(p, wl, rmsnorm(x, p["ln2"])), cache


def block_decode(p, wl, x, cache, pos, keep_rope: bool = True):
    a, cache = mla_decode(p["attn"], wl, rmsnorm(x, p["ln1"]), cache, pos,
                          keep_rope)
    x = x + a
    return x + _ffn(p, wl, rmsnorm(x, p["ln2"])), cache


def mtp_block(p, wl, h, emb_next, pos):
    """The MTP block: eh_proj over the normed hidden state and the normed
    embedding of the next token, then an MoE block."""
    x = jnp.concatenate([rmsnorm(h, p["norm_h"]),
                         rmsnorm(emb_next, p["norm_e"])], axis=-1)
    return block(p, wl, x @ p["eh_proj"], pos)[0]


def forward(params, wl, tokens):
    """Logits (B, S, vocab) of the dense then the MoE block; and the
    caches, one (latent, rope key) a block."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = params["embed"][tokens]
    x, c1 = block(params["dense"], wl, x, pos)
    x, c2 = block(params["moe"], wl, x, pos)
    return rmsnorm(x, params["final_ln"]) @ params["head"], (c1, c2)


def prefill(params, wl, tokens, max_len: int):
    """The whole-sequence forward, its caches padded to `max_len`."""
    logits, caches = forward(params, wl, tokens)
    pad = max_len - tokens.shape[1]
    caches = tuple(tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in c)
                   for c in caches)
    return logits, caches


def decode(params, wl, token, caches, pos, keep_rope: bool = True):
    """Logits (B, vocab) of one new token a sequence at positions pos."""
    x = params["embed"][token][:, None, :]
    x, c1 = block_decode(params["dense"], wl, x, caches[0], pos, keep_rope)
    x, c2 = block_decode(params["moe"], wl, x, caches[1], pos, keep_rope)
    return (rmsnorm(x, params["final_ln"]) @ params["head"])[:, 0], (c1, c2)
