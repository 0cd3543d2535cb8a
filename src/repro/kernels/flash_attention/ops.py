"""jit'd public wrapper for fused attention.

`mha()` routes between:
  - the Pallas flash kernel (aligned self-attention: train / prefill), and
  - the jnp masked oracle (decode-with-cache / arbitrary position vectors),
chosen by `use_pallas`. The train and serve launchers set it when JAX's
default backend is a TPU.

The kernel has no backward pass of its own: under `jax.grad` its VJP is the
oracle's, recomputed from the saved q/k/v (`_flash`), so training runs the
kernel forward and the jnp attention backward.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _ref_aligned(q, k, v, causal, window, softmax_scale):
    B, Sq = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32), (B, Sq))
    return attention_ref(q, k, v, pos, pos, causal=causal, window=window,
                         softmax_scale=softmax_scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, softmax_scale, interpret):
    return flash_attention(q, k, v, causal=causal, window=window,
                           softmax_scale=softmax_scale, interpret=interpret)


def _flash_fwd(q, k, v, causal, window, softmax_scale, interpret):
    return _flash(q, k, v, causal, window, softmax_scale, interpret), (q, k, v)


def _flash_bwd(causal, window, softmax_scale, interpret, res, g):
    _, vjp = jax.vjp(lambda q, k, v: _ref_aligned(q, k, v, causal, window,
                                                  softmax_scale), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def mha(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_pos: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    if use_pallas:
        # the kernel assumes aligned iota positions (self-attention)
        return _flash(q, k, v, causal, window, softmax_scale, interpret)
    return attention_ref(
        q, k, v, q_pos, kv_pos,
        causal=causal, window=window, softmax_scale=softmax_scale)
