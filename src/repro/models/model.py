"""Unified model facade for every assigned architecture.

Public API (all pure functions of (params, cfg, rt, ...)):
    init_params(rng, cfg)                      -> params pytree
    forward(params, cfg, rt, batch)            -> (logits, aux_loss)
    loss_fn(params, cfg, rt, batch)            -> (loss, metrics)
    init_cache(cfg, rt, batch_size, max_len)   -> cache pytree
    prefill(params, cfg, rt, batch, cache)     -> (last_logits, cache)
    decode_step(params, cfg, rt, tokens, pos, cache) -> (logits, cache)
    input_specs(cfg, shape)                    -> batch of ShapeDtypeStructs
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, hybrid
from repro.models.layers import embed_init, rmsnorm
from repro.models.mamba2 import (
    init_ssm_block,
    init_ssm_cache,
    ssm_block,
    ssm_block_decode,
    ssm_block_prefill,
)
from repro.models.runtime import Runtime
from repro.models.transformer import (
    decoder_stack,
    decoder_stack_decode,
    init_decoder_cache,
    init_decoder_layers,
)

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng, cfg: ModelConfig) -> Dict:
    ks = jax.random.split(rng, 4)
    p: Dict = {
        "embed": embed_init(ks[0], (cfg.vocab, cfg.d_model)),
        "final_ln": jnp.zeros((cfg.d_model,)),
    }
    if not cfg.tied_embeddings:
        p["unembed"] = embed_init(ks[1], (cfg.d_model, cfg.vocab))
    if cfg.family in ("dense", "moe", "vlm"):
        p["layers"] = init_decoder_layers(ks[2], cfg, cfg.num_layers)
    elif cfg.family == "ssm":
        p["layers"] = init_ssm_block(ks[2], cfg, (cfg.num_layers,))
    elif cfg.family == "hybrid":
        p["layers"] = hybrid.init_hybrid_layers(ks[2], cfg)
    elif cfg.family == "encdec":
        p["enc_layers"] = encdec.init_encoder_layers(ks[2], cfg)
        p["enc_ln"] = jnp.zeros((cfg.d_model,))
        p["dec_layers"] = encdec.init_decoder_layers_xattn(ks[3], cfg)
    else:
        raise ValueError(cfg.family)
    return p


def _embed(params, tokens, rt: Runtime):
    return params["embed"].astype(rt.compute_dtype)[tokens]


def _logits(params, x, rt: Runtime):
    xf = x.astype(jnp.float32)
    if "unembed" in params:
        return xf @ params["unembed"].astype(jnp.float32)
    return xf @ params["embed"].astype(jnp.float32).T


def _positions(B, S, start=0):
    pos = start + jnp.arange(S, dtype=jnp.int32)
    return jnp.broadcast_to(pos[None], (B, S))


def _ssm_stack(x, layers, cfg, rt):
    def body(xc, p_l):
        return ssm_block(xc, p_l, cfg, rt), None

    if rt.remat == "block":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, _ = jax.lax.scan(body, x, layers)
    return x


# ---------------------------------------------------------------------------
# forward / loss (training + full-sequence scoring)
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, rt: Runtime, batch: Dict
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    aux = jnp.float32(0.0)
    if cfg.family in ("dense", "moe"):
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = _embed(params, tokens, rt)
        x, aux = decoder_stack(x, params["layers"], cfg, rt,
                               _positions(B, S), cfg.num_layers)
    elif cfg.family == "vlm":
        tokens = batch["tokens"]                     # (B, S_text)
        patches = batch["patches"].astype(rt.compute_dtype)
        B = tokens.shape[0]
        x = jnp.concatenate([patches, _embed(params, tokens, rt)], axis=1)
        S = x.shape[1]
        x, aux = decoder_stack(x, params["layers"], cfg, rt,
                               _positions(B, S), cfg.num_layers,
                               prefix_len=cfg.prefix_len)
    elif cfg.family == "ssm":
        tokens = batch["tokens"]
        x = _embed(params, tokens, rt)
        x = _ssm_stack(x, params["layers"], cfg, rt)
    elif cfg.family == "hybrid":
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = _embed(params, tokens, rt)
        x, aux = hybrid.hybrid_forward(x, params["layers"], cfg, rt,
                                       _positions(B, S))
    elif cfg.family == "encdec":
        tokens = batch["tokens"]
        B, S = tokens.shape
        enc_out = encdec.encode(batch["frames"], params["enc_layers"], cfg, rt)
        enc_out = rmsnorm(enc_out, params["enc_ln"], cfg.norm_eps)
        x = _embed(params, tokens, rt)
        x = encdec.decode_stack(x, params["dec_layers"], cfg, rt,
                                _positions(B, S), enc_out)
    else:
        raise ValueError(cfg.family)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return _logits(params, x, rt), aux


def loss_fn(params, cfg: ModelConfig, rt: Runtime, batch: Dict,
            aux_weight: float = 0.01) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(params, cfg, rt, batch)
    labels = batch["labels"]
    if cfg.family == "vlm":
        # labels cover text positions only; prefix positions are ignored
        logits = logits[:, cfg.prefix_len:]
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / denom
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# cache / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, rt: Runtime, batch: int, max_len: int) -> Dict:
    if cfg.family in ("dense", "moe", "vlm"):
        return {"attn": init_decoder_cache(cfg, batch, max_len,
                                           cfg.num_layers, rt)}
    if cfg.family == "ssm":
        return {"ssm": init_ssm_cache(cfg, batch, cfg.num_layers, rt)}
    if cfg.family == "hybrid":
        return hybrid.init_hybrid_cache(cfg, batch, max_len, rt)
    if cfg.family == "encdec":
        return encdec.init_encdec_cache(cfg, batch, max_len, rt)
    raise ValueError(cfg.family)


def prefill(params, cfg: ModelConfig, rt: Runtime, batch: Dict, cache: Dict
            ) -> Tuple[jnp.ndarray, Dict]:
    """Fill the cache from position 0; returns (last-token logits, cache)."""
    pos0 = jnp.int32(0)
    if cfg.family in ("dense", "moe"):
        tokens = batch["tokens"]
        x = _embed(params, tokens, rt)
        x, attn_cache = decoder_stack_decode(
            x, params["layers"], cfg, rt, cache["attn"], pos0, cfg.num_layers,
            fresh=True)
        cache = {"attn": attn_cache}
    elif cfg.family == "vlm":
        tokens = batch["tokens"]
        patches = batch["patches"].astype(rt.compute_dtype)
        x = jnp.concatenate([patches, _embed(params, tokens, rt)], axis=1)
        x, attn_cache = decoder_stack_decode(
            x, params["layers"], cfg, rt, cache["attn"], pos0,
            cfg.num_layers, prefix_len=cfg.prefix_len, fresh=True)
        cache = {"attn": attn_cache}
    elif cfg.family == "ssm":
        tokens = batch["tokens"]
        x = _embed(params, tokens, rt)

        def body(xc, inp):
            p_l, c_l = inp
            xc, nc = ssm_block_prefill(xc, p_l, cfg, rt, c_l)
            return xc, nc

        x, ssm_cache = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
        cache = {"ssm": ssm_cache}
    elif cfg.family == "hybrid":
        tokens = batch["tokens"]
        x = _embed(params, tokens, rt)
        x, cache = hybrid.hybrid_prefill(x, params["layers"], cfg, rt,
                                         cache, pos0)
    elif cfg.family == "encdec":
        tokens = batch["tokens"]
        enc_out = encdec.encode(batch["frames"], params["enc_layers"], cfg, rt)
        enc_out = rmsnorm(enc_out, params["enc_ln"], cfg.norm_eps)
        cache = encdec.fill_cross_cache(enc_out, params["dec_layers"], cfg,
                                        rt, cache)
        x = _embed(params, tokens, rt)
        x, cache = encdec.decode_stack_cached(x, params["dec_layers"], cfg,
                                              rt, cache, pos0)
    else:
        raise ValueError(cfg.family)
    x = rmsnorm(x[:, -1:], params["final_ln"], cfg.norm_eps)
    return _logits(params, x, rt)[:, 0], cache


def decode_step(params, cfg: ModelConfig, rt: Runtime, tokens: jnp.ndarray,
                pos, cache: Dict) -> Tuple[jnp.ndarray, Dict]:
    """One autoregressive step. tokens (B, 1), pos scalar int32."""
    x = _embed(params, tokens, rt)
    if cfg.family in ("dense", "moe"):
        x, attn_cache = decoder_stack_decode(
            x, params["layers"], cfg, rt, cache["attn"], pos, cfg.num_layers)
        cache = {"attn": attn_cache}
    elif cfg.family == "vlm":
        x, attn_cache = decoder_stack_decode(
            x, params["layers"], cfg, rt, cache["attn"], pos,
            cfg.num_layers, prefix_len=cfg.prefix_len)
        cache = {"attn": attn_cache}
    elif cfg.family == "ssm":
        def body(xc, inp):
            p_l, c_l = inp
            xc, nc = ssm_block_decode(xc, p_l, cfg, rt, c_l)
            return xc, nc

        x, ssm_cache = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
        cache = {"ssm": ssm_cache}
    elif cfg.family == "hybrid":
        x, cache = hybrid.hybrid_decode(x, params["layers"], cfg, rt, cache,
                                        pos)
    elif cfg.family == "encdec":
        x, cache = encdec.decode_stack_cached(x, params["dec_layers"], cfg,
                                              rt, cache, pos)
    else:
        raise ValueError(cfg.family)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return _logits(params, x, rt)[:, 0], cache


# ---------------------------------------------------------------------------
# input specs (dry-run stand-ins; no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    """ShapeDtypeStruct stand-ins for the batch of a given shape cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
    elif shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    else:  # decode: one new token against a cache of length S
        batch = {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    if cfg.family == "encdec":
        batch["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_len, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm" and shape.kind != "decode":
        batch["patches"] = jax.ShapeDtypeStruct(
            (B, cfg.prefix_len, cfg.d_model), jnp.bfloat16)
        # text portion shrinks so total sequence == shape.seq_len
        text = S - cfg.prefix_len
        batch["tokens"] = jax.ShapeDtypeStruct((B, text), i32)
        if shape.kind == "train":
            batch["labels"] = jax.ShapeDtypeStruct((B, text), i32)
    return batch
