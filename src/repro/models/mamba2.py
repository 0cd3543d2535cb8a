"""Mamba-2 (SSD) block: in_proj -> causal depthwise conv -> SSD -> gated norm
-> out_proj. Full-sequence (chunked scan / Pallas kernel) and single-token
recurrent decode paths. [arXiv:2405.21060]
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.models.layers import causal_depthwise_conv, dense_init, gated_rmsnorm, rmsnorm
from repro.models.runtime import Runtime, kernel_shard_axes


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    H = s.n_heads(cfg.d_model)
    return s, di, H, s.head_dim, s.state_dim


def init_ssm_block(key, cfg: ModelConfig, stack: tuple = ()) -> dict:
    s, di, H, P, N = _dims(cfg)
    D = cfg.d_model
    conv_ch = di + 2 * N
    proj_out = 2 * di + 2 * N + H          # z, x, B, C, dt
    ks = jax.random.split(key, 4)
    return {
        "ln": jnp.zeros((*stack, D)),
        "in_proj": dense_init(ks[0], (*stack, D, proj_out)),
        "conv_w": dense_init(ks[1], (*stack, s.conv_width, conv_ch), scale=0.3),
        "conv_b": jnp.zeros((*stack, conv_ch)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (*stack, H)).copy(),
        "D": jnp.ones((*stack, H)),
        "dt_bias": jnp.broadcast_to(
            jnp.log(jnp.expm1(0.01 * jnp.ones(H))), (*stack, H)).copy(),
        "norm": jnp.zeros((*stack, di)),
        "out_proj": dense_init(ks[2], (*stack, di, D)),
    }


def _split_proj(proj, cfg: ModelConfig):
    _, di, H, _, N = _dims(cfg)
    z = proj[..., :di]
    x = proj[..., di:2 * di]
    Bm = proj[..., 2 * di:2 * di + N]
    Cm = proj[..., 2 * di + N:2 * di + 2 * N]
    dt = proj[..., 2 * di + 2 * N:]
    return z, x, Bm, Cm, dt


def _constrain_heads(xh: jnp.ndarray, rt: Runtime) -> jnp.ndarray:
    """SSM tensor parallelism: SSD heads over `model`, batch over dp — each
    head's (P, N) recurrence is independent, so this is the clean TP axis
    (B/C are head-shared and stay replicated)."""
    if rt.mesh_axes is None or not rt.opt_ssm_head_tp:
        return xh
    from jax.sharding import PartitionSpec as P_

    axes = rt.mesh_axes
    dp = tuple(a for a in ("pod", "data") if a in axes)
    dp_size = 1
    for a in dp:
        dp_size *= axes[a]
    model = axes.get("model", 1)
    B, _, H = xh.shape[:3]
    batch_axes = dp if (dp_size > 1 and B % dp_size == 0) else None
    head_axes = "model" if (model > 1 and H % model == 0) else None
    spec = (P_(batch_axes, None, head_axes, None) if xh.ndim == 4
            else P_(batch_axes, None, head_axes))
    return jax.lax.with_sharding_constraint(xh, spec)


def _ssd(xh, dtv, A, Bm, Cm, D, rt: Runtime):
    """The chunked SSD scan. With `rt.use_pallas` under a mesh the kernel
    runs per shard (`kernel_shard_axes`): batch over the data axes, heads
    over `model` when they divide it."""
    def scan(xh, dtv, A, Bm, Cm, D):
        return ssd_ops.ssd(xh, dtv, A, Bm, Cm, D, chunk=rt.ssd_chunk,
                           use_pallas=rt.use_pallas, interpret=rt.interpret)

    if not rt.use_pallas or rt.mesh_axes is None:
        return scan(xh, dtv, A, Bm, Cm, D)
    from jax.sharding import PartitionSpec as P_
    dp, h = kernel_shard_axes(rt, xh.shape[0], (xh.shape[2],))
    in_specs = (P_(dp, None, h, None), P_(dp, None, h), P_(h),
                P_(dp, None, None), P_(dp, None, None), P_(h))
    out_specs = (P_(dp, None, h, None), P_(dp, h, None, None))
    return jax.shard_map(scan, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)(xh, dtv, A, Bm, Cm, D)


def ssm_block(x: jnp.ndarray, p: dict, cfg: ModelConfig, rt: Runtime
              ) -> jnp.ndarray:
    """Full-sequence forward. x (B, S, D) -> (B, S, D) residual added."""
    s, di, H, P, N = _dims(cfg)
    B, S, D = x.shape
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    proj = h @ p["in_proj"].astype(rt.compute_dtype)
    z, xs, Bm, Cm, dt = _split_proj(proj, cfg)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = causal_depthwise_conv(conv_in, p["conv_w"], p["conv_b"])
    xs, Bm, Cm = (conv_out[..., :di], conv_out[..., di:di + N],
                  conv_out[..., di + N:])

    xh = _constrain_heads(xs.reshape(B, S, H, P), rt)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, _ = _ssd(xh, dtv, A, Bm, Cm, p["D"], rt)
    y = y.reshape(B, S, di)
    y = gated_rmsnorm(y, z, p["norm"], cfg.norm_eps)
    return x + y @ p["out_proj"].astype(rt.compute_dtype)


def ssm_block_prefill(x: jnp.ndarray, p: dict, cfg: ModelConfig, rt: Runtime,
                      cache_l: dict) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence forward that also captures the decode cache (final SSD
    state + conv tail). x (B, S, D)."""
    s, di, H, P, N = _dims(cfg)
    B, S, D = x.shape
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    proj = h @ p["in_proj"].astype(rt.compute_dtype)
    z, xs, Bm, Cm, dt = _split_proj(proj, cfg)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = causal_depthwise_conv(conv_in, p["conv_w"], p["conv_b"])
    K = s.conv_width
    if S >= K - 1:
        new_conv = conv_in[:, S - (K - 1):, :].astype(rt.compute_dtype)
    else:
        new_conv = jnp.concatenate(
            [cache_l["conv"][:, S:], conv_in.astype(rt.compute_dtype)], axis=1)
    xs, Bm, Cm = (conv_out[..., :di], conv_out[..., di:di + N],
                  conv_out[..., di + N:])
    xh = _constrain_heads(xs.reshape(B, S, H, P), rt)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, hT = _ssd(xh, dtv, A, Bm, Cm, p["D"], rt)
    y = y.reshape(B, S, di)
    y = gated_rmsnorm(y, z, p["norm"], cfg.norm_eps)
    out = x + y @ p["out_proj"].astype(rt.compute_dtype)
    return out, {"conv": new_conv, "ssd": hT}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def init_ssm_cache(cfg: ModelConfig, batch: int, n_layers: int, rt: Runtime
                   ) -> dict:
    s, di, H, P, N = _dims(cfg)
    conv_ch = di + 2 * N
    return {
        "conv": jnp.zeros((n_layers, batch, s.conv_width - 1, conv_ch),
                          rt.compute_dtype),
        "ssd": jnp.zeros((n_layers, batch, H, P, N), jnp.float32),
    }


def ssm_block_decode(x: jnp.ndarray, p: dict, cfg: ModelConfig, rt: Runtime,
                     cache_l: dict) -> Tuple[jnp.ndarray, dict]:
    """Single-token recurrent step. x (B, 1, D)."""
    s, di, H, P, N = _dims(cfg)
    B = x.shape[0]
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    proj = h @ p["in_proj"].astype(rt.compute_dtype)
    z, xs, Bm, Cm, dt = _split_proj(proj, cfg)

    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)       # (B, 1, ch)
    conv_out = causal_depthwise_conv(
        conv_in, p["conv_w"], p["conv_b"], state=cache_l["conv"])
    new_conv = jnp.concatenate([cache_l["conv"][:, 1:], conv_in], axis=1)

    xs, Bm, Cm = (conv_out[..., :di], conv_out[..., di:di + N],
                  conv_out[..., di + N:])
    xh = xs.reshape(B, H, P)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"].astype(jnp.float32))[:, 0]   # (B, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, new_state = ssd_ops.ssd_decode_step(
        cache_l["ssd"], xh, dtv, A, Bm[:, 0], Cm[:, 0], p["D"])
    y = y.reshape(B, 1, di)
    y = gated_rmsnorm(y, z, p["norm"], cfg.norm_eps)
    out = x + y @ p["out_proj"].astype(rt.compute_dtype)
    return out, {"conv": new_conv, "ssd": new_state}
