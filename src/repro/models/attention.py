"""Attention module glue: projections + RoPE + kernel dispatch + KV caches.

Caches are position-explicit: every cache keeps a `kv_pos` int32 array beside
k/v so ring-buffer (sliding-window) caches and full caches share one masked
attention path (see kernels/flash_attention/ref.make_mask).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref, make_mask
from repro.models.layers import dense_init, rope
from repro.models.runtime import Runtime, kernel_shard_axes


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, stack: tuple = ()) -> dict:
    D, hd = cfg.d_model, cfg.hd()
    nq, nkv = cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (*stack, D, nq * hd)),
        "wk": dense_init(ks[1], (*stack, D, nkv * hd)),
        "wv": dense_init(ks[2], (*stack, D, nkv * hd)),
        "wo": dense_init(ks[3], (*stack, nq * hd, D)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((*stack, nq * hd))
        p["bk"] = jnp.zeros((*stack, nkv * hd))
        p["bv"] = jnp.zeros((*stack, nkv * hd))
    return p


def _constrain_attn(x: jnp.ndarray, rt: Runtime, is_query: bool
                    ) -> jnp.ndarray:
    """Divisibility-aware constraint on (B, S, H, hd) attention activations:
    head-parallel over `model` when H divides it, sequence-parallel for q
    otherwise (always legal for our seq lengths), batch over dp axes when
    divisible. k/v that cannot head-shard stay batch-only — the GQA repeat
    resolves against head-sharded q. Without this, SPMD can replicate
    full-batch attention tensors when the flat H*hd weight sharding cuts
    head boundaries (e.g. 9-head smollm on a 16-wide model axis)."""
    if rt.mesh_axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    axes = rt.mesh_axes
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_size = 1
    for a in dp:
        dp_size *= axes[a]
    model = axes.get("model", 1)
    B, S, H, _ = x.shape
    batch_axes = dp if (dp_size > 1 and B % dp_size == 0) else None
    if model > 1 and H % model == 0:
        spec = P(batch_axes, None, "model", None)
    elif is_query and model > 1 and S % model == 0 and S >= model:
        spec = P(batch_axes, "model", None, None)
    else:
        spec = P(batch_axes, None, None, None)
    return jax.lax.with_sharding_constraint(x, spec)


def _flash(q, k, v, cfg: ModelConfig, rt: Runtime, *, causal: bool,
           window: Optional[int]) -> jnp.ndarray:
    """Aligned self-attention on the Pallas flash kernel. Under a mesh the
    kernel runs per shard (`kernel_shard_axes`): batch over the data axes,
    heads over `model` when both the q and the kv head counts divide it."""
    def attend(q, k, v):
        return fa_ops.mha(q, k, v, None, None, causal=causal, window=window,
                          use_pallas=True, interpret=rt.interpret)

    if rt.mesh_axes is None:
        return attend(q, k, v)
    from jax.sharding import PartitionSpec as P
    dp, head = kernel_shard_axes(rt, q.shape[0], (cfg.n_heads, cfg.n_kv))
    spec = P(dp, None, head, None)
    return jax.shard_map(attend, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _proj_qkv(h, p, cfg: ModelConfig, rt: Runtime):
    B, S, _ = h.shape
    hd = cfg.hd()
    q = h @ p["wq"].astype(rt.compute_dtype)
    k = h @ p["wk"].astype(rt.compute_dtype)
    v = h @ p["wv"].astype(rt.compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(rt.compute_dtype)
        k = k + p["bk"].astype(rt.compute_dtype)
        v = v + p["bv"].astype(rt.compute_dtype)
    q = _constrain_attn(q.reshape(B, S, cfg.n_heads, hd), rt, True)
    k = _constrain_attn(k.reshape(B, S, cfg.n_kv, hd), rt, False)
    v = _constrain_attn(v.reshape(B, S, cfg.n_kv, hd), rt, False)
    return q, k, v


# ---------------------------------------------------------------------------
# full-sequence self attention (train / prefill)
# ---------------------------------------------------------------------------


def self_attention(
    h: jnp.ndarray,                   # (B, S, D)
    p: dict,
    cfg: ModelConfig,
    rt: Runtime,
    positions: jnp.ndarray,           # (B, S)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,              # prefix-LM: bidirectional first P tokens
) -> jnp.ndarray:
    q, k, v = _proj_qkv(h, p, cfg, rt)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if prefix_len > 0:
        # prefix-LM mask needs the general masked path
        out = _prefix_lm_attention(q, k, v, positions, prefix_len)
    elif rt.use_pallas:
        out = _flash(q, k, v, cfg, rt, causal=causal, window=window)
    else:
        out = fa_ops.mha(q, k, v, positions, positions, causal=causal,
                         window=window)
    B, S = h.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.hd())
    return out @ p["wo"].astype(rt.compute_dtype)


def _prefix_lm_attention(q, k, v, positions, prefix_len):
    base = make_mask(positions, positions, causal=True, window=None)
    prefix = positions[:, None, :] < prefix_len          # kv inside prefix
    both_prefix = prefix & (positions[:, :, None] < prefix_len)
    mask = base | both_prefix
    return _masked_attention(q, k, v, mask)


def _masked_attention(q, k, v, mask):
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    qf = q.astype(jnp.float32) * hd ** -0.5
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    if rep > 1:
        kf = jnp.repeat(kf, rep, axis=2)
        vf = jnp.repeat(vf, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    s = jnp.where(mask[:, None], s, -1e30)
    pm = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", pm, vf).astype(q.dtype)


# query-chunking threshold: chunk whenever the scores tensor would exceed
# ~Sq*Skv elements per (batch, head). Keeps prefill-32k/500k from
# materializing O(S^2) scores — the jnp analogue of flash blocking, with the
# same HBM traffic profile (K/V re-read once per q chunk).
_CHUNK_Q = 512
_CHUNK_THRESHOLD = 8192


def _attention_bf16_scores(q, k, v, q_pos, kv_pos, *, causal, window,
                           prefix_len=0):
    """attention_ref with bf16 score matmuls + fp32 MXU accumulation: no
    materialized fp32 Q/K/V copies (§Perf OPT-D). Same mask semantics."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    kf, vf = k, v
    if rep > 1:
        kf = jnp.repeat(kf, rep, axis=2)
        vf = jnp.repeat(vf, rep, axis=2)
    qs = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qs, kf,
                        preferred_element_type=jnp.float32)
    mask = make_mask(q_pos, kv_pos, causal=causal, window=window,
                     prefix_len=prefix_len)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), vf,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _attend(q, k, v, q_pos, kv_pos, *, causal, window, prefix_len=0,
            bf16_scores=False):
    """Masked attention with automatic q-chunking for long sequences."""
    attn = _attention_bf16_scores if bf16_scores else attention_ref
    Sq = q.shape[1]
    if Sq < _CHUNK_THRESHOLD or Sq % _CHUNK_Q != 0:
        return attn(q, k, v, q_pos, kv_pos, causal=causal,
                    window=window, prefix_len=prefix_len)
    nq = Sq // _CHUNK_Q

    def chunk_fn(_, inp):
        qc, qpc = inp
        out = attn(qc, k, v, qpc, kv_pos, causal=causal,
                   window=window, prefix_len=prefix_len)
        return None, out

    qs = q.reshape(q.shape[0], nq, _CHUNK_Q, *q.shape[2:]).transpose(1, 0, 2, 3, 4)
    qps = q_pos.reshape(q_pos.shape[0], nq, _CHUNK_Q).transpose(1, 0, 2)
    _, outs = jax.lax.scan(chunk_fn, None, (qs, qps))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(q.shape)
    return out


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_layers: int, rt: Runtime,
                  window: Optional[int] = None) -> dict:
    """Cache for `n_layers` attention layers. With rt.ring_cache and a window,
    the buffer is only `window` slots (ring); otherwise full `max_len`."""
    W = max_len
    if rt.ring_cache and window is not None:
        W = min(window, max_len)
    hd = cfg.hd()
    return {
        "k": jnp.zeros((n_layers, batch, W, cfg.n_kv, hd), rt.compute_dtype),
        "v": jnp.zeros((n_layers, batch, W, cfg.n_kv, hd), rt.compute_dtype),
        "kv_pos": jnp.full((n_layers, batch, W), -1, jnp.int32),
    }


def _pos_vector(pos, B: int) -> jnp.ndarray:
    """Normalize scalar-or-(B,) position to (B,) int32 (per-slot positions
    enable continuous batching in the serving engine)."""
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        p = jnp.broadcast_to(p, (B,))
    return p


def update_cache_layer(cache_l: dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                       pos, use_dus: bool = True) -> dict:
    """Insert S_new tokens starting at absolute position `pos` (scalar or
    per-batch (B,)) into a layer cache (B, W, Hkv, hd). Ring index = pos % W.

    Scalar `pos` with a contiguous non-wrapping span uses
    dynamic-update-slice: under SPMD a DUS keeps a sequence-sharded cache
    sharded (each shard masks locally), whereas a scatter forces the
    partitioner to all-gather the whole cache (measured: 291 GB/chip per
    decode step on gemma3-4b long_500k — see EXPERIMENTS.md §Perf).
    use_dus=False reproduces the scatter baseline."""
    B, W = cache_l["k"].shape[:2]
    S_new = k_new.shape[1]
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0 and use_dus:
        start = p % W
        # wrapping spans fall back to scatter (prefill into small ring);
        # S_new == 1 (decode) or aligned prefill never wraps
        if S_new == 1 or W % S_new == 0:
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache_l["k"], k_new, start, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache_l["v"], v_new, start, axis=1)
            positions = (p + jnp.arange(S_new, dtype=jnp.int32))[None, :]
            pc = jax.lax.dynamic_update_slice_in_dim(
                cache_l["kv_pos"],
                jnp.broadcast_to(positions, (B, S_new)), start, axis=1)
            return {"k": kc, "v": vc, "kv_pos": pc}
    pv = _pos_vector(pos, B)                              # (B,)
    positions = pv[:, None] + jnp.arange(S_new)[None, :]  # (B, S_new)
    slots = positions % W
    bidx = jnp.arange(B)[:, None]
    kc = cache_l["k"].at[bidx, slots].set(k_new)
    vc = cache_l["v"].at[bidx, slots].set(v_new)
    pc = cache_l["kv_pos"].at[bidx, slots].set(positions.astype(jnp.int32))
    return {"k": kc, "v": vc, "kv_pos": pc}


def cached_attention(
    x: jnp.ndarray,                   # (B, S_new, D) new tokens' hidden
    p: dict,
    cfg: ModelConfig,
    rt: Runtime,
    cache_l: dict,
    pos: jnp.ndarray,                 # scalar: absolute position of x[:, 0]
    *,
    window: Optional[int] = None,
    prefix_len: int = 0,
    fresh: bool = False,
) -> Tuple[jnp.ndarray, dict]:
    """Decode/chunked-prefill attention against a (possibly ring) cache.
    `pos` may be a scalar or a per-slot (B,) vector. `fresh` marks a
    prefill from position 0 into an empty cache: the new tokens attend only
    to each other, so with `rt.use_pallas` it runs as aligned
    self-attention on the flash kernel."""
    B, S_new, _ = x.shape
    q, k, v = _proj_qkv(x, p, cfg, rt)
    pv = _pos_vector(pos, B)
    positions = (pv[:, None] + jnp.arange(S_new)[None, :]).astype(jnp.int32)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    cache_l = update_cache_layer(cache_l, k, v, pos,
                                 use_dus=rt.opt_cache_dus)
    W = cache_l["k"].shape[1]
    p_scalar = jnp.asarray(pos).ndim == 0
    # is the cache sequence-sharded? (B too small to take the dp axes) —
    # then a dynamic-slice would force SPMD to gather the cache, so the
    # masked flash-decoding path (which never gathers) must win.
    seq_sharded = False
    if rt.mesh_axes is not None:
        dp_size = 1
        for a in ("pod", "data"):
            dp_size *= rt.mesh_axes.get(a, 1)
        seq_sharded = B % dp_size != 0 and W >= 65536
    if fresh and rt.use_pallas and prefix_len == 0:
        out = _flash(q, k, v, cfg, rt, causal=True, window=window)
    elif (S_new == 1 and rt.mesh_axes is not None and rt.opt_cache_dus
            and seq_sharded):
        out = _long_decode_attention(
            q, cache_l["k"], cache_l["v"], positions, cache_l["kv_pos"],
            rt, window=window)
    elif (rt.opt_cache_dus and p_scalar and S_new == 1
            and window is not None and W >= 4 * window):
        # windowed decode against a long batch-sharded cache: slice the
        # last `window` slots instead of reading (and masking) the whole
        # cache — the decode-side analogue of a ring buffer. O(W) ->
        # O(window) HBM reads (EXPERIMENTS.md §Perf OPT-A).
        start = jnp.clip(jnp.asarray(pos, jnp.int32) - window + 1, 0,
                         W - window)
        k_win = jax.lax.dynamic_slice_in_dim(cache_l["k"], start, window, 1)
        v_win = jax.lax.dynamic_slice_in_dim(cache_l["v"], start, window, 1)
        pos_win = jax.lax.dynamic_slice_in_dim(cache_l["kv_pos"], start,
                                               window, 1)
        out = _attend(q, k_win, v_win, positions, pos_win,
                      causal=True, window=window, prefix_len=prefix_len,
                      bf16_scores=rt.opt_bf16_scores)
    elif (S_new == 1 and W >= 65536 and rt.mesh_axes is not None
            and rt.opt_cache_dus):
        # long-context decode: flash-decoding-style sequence-parallel
        # attention (scores stay sharded on the cache's sequence dim; no
        # GQA repeat — see EXPERIMENTS.md §Perf OPT-A)
        out = _long_decode_attention(
            q, cache_l["k"], cache_l["v"], positions, cache_l["kv_pos"],
            rt, window=window)
    else:
        out = _attend(
            q, cache_l["k"], cache_l["v"], positions, cache_l["kv_pos"],
            causal=True, window=window, prefix_len=prefix_len,
            bf16_scores=rt.opt_bf16_scores)
    out = out.reshape(B, S_new, cfg.n_heads * cfg.hd())
    return out @ p["wo"].astype(rt.compute_dtype), cache_l


def _long_decode_attention(q, k, v, q_pos, kv_pos, rt: Runtime,
                           window: Optional[int] = None) -> jnp.ndarray:
    """One-token attention against a sequence-sharded cache without ever
    materializing a gathered K/V: grouped-head einsum (no jnp.repeat — the
    repeat's reshard is what forced SPMD to all-gather the fp32 cache) with
    explicit seq-sharded score constraints. Softmax/combine reductions over
    the sharded dim lower to tiny all-reduces (flash-decoding on SPMD)."""
    from jax.sharding import PartitionSpec as P

    B, Sq, Hq, hd = q.shape
    _, W, Hkv, _ = k.shape
    rep = Hq // Hkv
    axes = rt.mesh_axes
    dp = tuple(dpx for dpx in ("pod", "data") if dpx in axes)
    model = axes.get("model", 1)
    dp_size = 1
    for a in dp:
        dp_size *= axes[a]
    if model > 1 and Hkv % model == 0 and W % max(dp_size, 1) == 0:
        # KV heads shard over model (matches the cache's resident sharding
        # for wide-GQA archs — no reshard), sequence over dp
        kspec = P(None, dp if dp_size > 1 else None, "model", None)
        head_axes: Optional[str] = "model"
        seq_axes = dp
    else:
        seq_axes = tuple(dp) + ("model",)
        head_axes = None
        seq_ok = W % max(
            1, int(np.prod([axes[a] for a in seq_axes]))) == 0
        kspec = P(None, seq_axes if seq_ok else None, None, None)

    # keep K/V in their storage dtype — an fp32 upcast would materialize a
    # second copy of the whole cache in HBM (measured 51 GB/chip); the MXU
    # accumulates in fp32 via preferred_element_type
    qf = (q.astype(jnp.float32) * hd ** -0.5).astype(q.dtype)
    qf = qf.reshape(B, Hkv, rep, hd)
    kf = jax.lax.with_sharding_constraint(k, kspec)
    vf = jax.lax.with_sharding_constraint(v, kspec)
    scores = jnp.einsum("bgrd,bsgd->bgrs", qf, kf,
                        preferred_element_type=jnp.float32)  # (B,Hkv,rep,W)
    seq_ok = W % max(
        1, int(np.prod([axes[a] for a in seq_axes]))) == 0 if seq_axes else False
    sspec = P(None, head_axes, None,
              seq_axes if (seq_ok and seq_axes) else None)
    scores = jax.lax.with_sharding_constraint(scores, sspec)

    kv = kv_pos[:, None, None, :]                        # (B,1,1,W)
    qp = q_pos[:, 0][:, None, None, None]
    mask = (kv >= 0) & (kv <= qp)
    if window is not None:
        mask = mask & (kv > qp - window)
    scores = jnp.where(mask, scores, -1e30)
    m = jnp.max(scores, axis=-1, keepdims=True)          # psum over shards
    p_ = jnp.where(mask, jnp.exp(scores - m), 0.0)
    l = jnp.maximum(jnp.sum(p_, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bgrs,bsgd->bgrd", (p_ / l).astype(v.dtype), vf,
                     preferred_element_type=jnp.float32)  # partial+psum
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# cross attention (enc-dec)
# ---------------------------------------------------------------------------


def init_cross_attention(key, cfg: ModelConfig, stack: tuple = ()) -> dict:
    return init_attention(key, cfg, stack)


def cross_attention(
    x: jnp.ndarray,                   # (B, Sq, D) decoder hidden
    p: dict,
    cfg: ModelConfig,
    rt: Runtime,
    enc_k: jnp.ndarray,               # (B, Senc, Hkv, hd) precomputed
    enc_v: jnp.ndarray,
) -> jnp.ndarray:
    B, Sq, _ = x.shape
    hd = cfg.hd()
    q = x @ p["wq"].astype(rt.compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(rt.compute_dtype)
    q = q.reshape(B, Sq, cfg.n_heads, hd)
    Senc = enc_k.shape[1]
    qpos = jnp.zeros((B, Sq), jnp.int32)
    kvpos = jnp.broadcast_to(jnp.arange(Senc)[None], (B, Senc)).astype(jnp.int32)
    out = _attend(q, enc_k, enc_v, qpos, kvpos, causal=False, window=None)
    out = out.reshape(B, Sq, cfg.n_heads * hd)
    return out @ p["wo"].astype(rt.compute_dtype)


def encode_cross_kv(enc_out: jnp.ndarray, p: dict, cfg: ModelConfig,
                    rt: Runtime) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Project encoder output once into cross-attention K/V."""
    B, Senc, _ = enc_out.shape
    hd = cfg.hd()
    k = enc_out @ p["wk"].astype(rt.compute_dtype)
    v = enc_out @ p["wv"].astype(rt.compute_dtype)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(rt.compute_dtype)
        v = v + p["bv"].astype(rt.compute_dtype)
    return (k.reshape(B, Senc, cfg.n_kv, hd),
            v.reshape(B, Senc, cfg.n_kv, hd))
