"""Decoder stacks (dense + MoE) with scan-over-layers and per-layer remat.

One block implementation serves dense (llama/qwen/smollm), local:global
patterned (gemma3), MoE (mixtral/grok) and VLM-decoder (paligemma) archs.
Params are stacked along a leading L dim so the stack is a single
`jax.lax.scan` — compile time is O(1) in depth.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (
    cached_attention,
    init_attention,
    init_kv_cache,
    self_attention,
)
from repro.models.layers import init_mlp, mlp, rmsnorm
from repro.models.moe import init_moe, moe_mlp
from repro.models.runtime import Runtime


def global_flags(cfg: ModelConfig, n_layers: int) -> Optional[jnp.ndarray]:
    """(L,) bool: True where the layer uses global (full) attention."""
    if cfg.local_global_pattern is None:
        return None
    loc, glob = cfg.local_global_pattern
    period = loc + glob
    idx = jnp.arange(n_layers)
    return (idx % period) >= loc


def init_decoder_layers(key, cfg: ModelConfig, n_layers: int) -> dict:
    ks = jax.random.split(key, 2)
    stack = (n_layers,)
    p = {
        "ln1": jnp.zeros((n_layers, cfg.d_model)),
        "attn": init_attention(ks[0], cfg, stack),
        "ln2": jnp.zeros((n_layers, cfg.d_model)),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(ks[1], cfg, stack)
    else:
        p["mlp"] = init_mlp(ks[1], cfg, cfg.d_ff, stack)
    return p


def _attn_with_pattern(h, p_l, cfg: ModelConfig, rt: Runtime, positions,
                       flag, prefix_len):
    """Dispatch local(window) vs global attention on a traced per-layer flag."""
    if flag is None:
        return self_attention(h, p_l, cfg, rt, positions,
                              window=cfg.sliding_window, prefix_len=prefix_len)
    return jax.lax.cond(
        flag,
        lambda hh: self_attention(hh, p_l, cfg, rt, positions,
                                  window=None, prefix_len=prefix_len),
        lambda hh: self_attention(hh, p_l, cfg, rt, positions,
                                  window=cfg.sliding_window,
                                  prefix_len=prefix_len),
        h)


def decoder_block(x, p_l, cfg: ModelConfig, rt: Runtime, positions,
                  flag, prefix_len: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pre-norm block. Returns (x, aux_loss)."""
    h = rmsnorm(x, p_l["ln1"], cfg.norm_eps)
    x = x + _attn_with_pattern(h, p_l["attn"], cfg, rt, positions, flag,
                               prefix_len)
    h = rmsnorm(x, p_l["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        out, aux = moe_mlp(h, p_l["moe"], cfg, rt)
    else:
        out, aux = mlp(h, p_l["mlp"], cfg, rt), jnp.float32(0.0)
    return x + out, aux


def decoder_stack(x, layers: dict, cfg: ModelConfig, rt: Runtime, positions,
                  n_layers: int, prefix_len: int = 0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan the stack. x (B, S, D) -> (x, total_aux_loss)."""
    flags = global_flags(cfg, n_layers)

    def body(carry, inp):
        xc, aux = carry
        p_l, flag = inp
        xc, a = decoder_block(xc, p_l, cfg, rt, positions, flag, prefix_len)
        return (xc, aux + a), None

    if rt.remat == "block":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    xs = (layers, flags if flags is not None
          else jnp.zeros((n_layers,), jnp.int32))
    if flags is None:
        def body_noflag(carry, p_l):
            return body(carry, (p_l, None))
        bodyfn, xs = body_noflag, layers
        if rt.remat == "block":
            # body already rematted; wrap shim only
            pass
    else:
        bodyfn = body

    (x, aux), _ = jax.lax.scan(bodyfn, (x, jnp.float32(0.0)), xs)
    return x, aux


# ---------------------------------------------------------------------------
# decode (one or few tokens against per-layer caches)
# ---------------------------------------------------------------------------


def init_decoder_cache(cfg: ModelConfig, batch: int, max_len: int,
                       n_layers: int, rt: Runtime) -> dict:
    window = cfg.sliding_window if cfg.local_global_pattern is None else None
    # patterned archs keep full-length caches in the baseline (see DESIGN §5)
    return init_kv_cache(cfg, batch, max_len, n_layers, rt, window=window)


def decoder_block_decode(x, p_l, cfg: ModelConfig, rt: Runtime, cache_l,
                         pos, flag, prefix_len: int = 0, fresh: bool = False
                         ) -> Tuple[jnp.ndarray, dict, jnp.ndarray]:
    h = rmsnorm(x, p_l["ln1"], cfg.norm_eps)
    attn = functools.partial(cached_attention, p=p_l["attn"], cfg=cfg, rt=rt,
                             pos=pos, prefix_len=prefix_len, fresh=fresh)
    if flag is None:
        a_out, cache_l = attn(h, cache_l=cache_l, window=cfg.sliding_window)
    else:
        a_out, cache_l = jax.lax.cond(
            flag,
            lambda hh, cc: attn(hh, cache_l=cc, window=None),
            lambda hh, cc: attn(hh, cache_l=cc, window=cfg.sliding_window),
            h, cache_l)
    x = x + a_out
    h = rmsnorm(x, p_l["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        out, aux = moe_mlp(h, p_l["moe"], cfg, rt)
    else:
        out, aux = mlp(h, p_l["mlp"], cfg, rt), jnp.float32(0.0)
    return x + out, cache_l, aux


def decoder_stack_decode(x, layers: dict, cfg: ModelConfig, rt: Runtime,
                         cache: dict, pos, n_layers: int,
                         prefix_len: int = 0, fresh: bool = False
                         ) -> Tuple[jnp.ndarray, dict]:
    """Scan the stack over per-layer caches; `fresh` is a prefill from
    position 0 into an empty cache (see `cached_attention`)."""
    flags = global_flags(cfg, n_layers)

    def body(xc, inp):
        if flags is None:
            p_l, cache_l = inp
            flag = None
        else:
            p_l, cache_l, flag = inp
        xc, cache_l, _ = decoder_block_decode(xc, p_l, cfg, rt, cache_l, pos,
                                              flag, prefix_len, fresh)
        return xc, cache_l

    xs = (layers, cache) if flags is None else (layers, cache, flags)
    x, new_cache = jax.lax.scan(body, x, xs)
    return x, new_cache
