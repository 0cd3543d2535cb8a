"""jit'd public wrapper for the SSD scan.

The Pallas kernel has no backward pass of its own: under `jax.grad` its VJP
is the chunked oracle's, recomputed from the saved inputs (`_ssd_pallas`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_chunked_ref, ssd_decode_step_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_pallas(x, dt, A, Bm, Cm, D, chunk, interpret):
    return ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dt, A, Bm, Cm, D, chunk, interpret):
    out = _ssd_pallas(x, dt, A, Bm, Cm, D, chunk, interpret)
    return out, (x, dt, A, Bm, Cm, D)


def _ssd_bwd(chunk, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(ssd_chunked_ref, chunk=chunk), *res)
    return vjp(g)


_ssd_pallas.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    D: Optional[jnp.ndarray] = None,
    *,
    chunk: int = 128,
    use_pallas: bool = False,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if use_pallas:
        return _ssd_pallas(x, dt, A, Bm, Cm, D, chunk, interpret)
    return ssd_chunked_ref(x, dt, A, Bm, Cm, D, chunk=chunk)


ssd_decode_step = ssd_decode_step_ref
