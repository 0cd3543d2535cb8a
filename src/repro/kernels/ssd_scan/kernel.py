"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

TPU-native design:
  - grid (B, H, nC) with the chunk dimension LAST: TPU grids iterate the
    trailing dim sequentially, so the inter-chunk SSM state (P, N) is carried
    in VMEM scratch across chunk steps of one (b, h) program instance.
  - per step, one chunk of x (Q, P), dt (Q, 1), B/C (Q, N) is tiled into VMEM;
    the intra-chunk quadratic form runs on the MXU as (Q,N)x(N,Q) and
    (Q,Q)x(Q,P) matmuls — Q=128, P=64/128, N=64/128 are all MXU-aligned.
  - decay terms use cumulative-log-sum within the chunk (fp32), matching
    ssd_chunked_ref exactly.

The segment-sum decay matrix is the memory hot spot of SSD on GPUs; on TPU we
never materialize it in HBM — it lives only as a (Q, Q) VMEM tile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref,
                h_scr, *, Q: int, P: int, N: int, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q, 1)
    a = a_ref[0, 0]                              # (1, 1) fp32 (negative)
    bmat = b_ref[0].astype(jnp.float32)          # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)          # (Q, N)

    da = dt * a[0, 0]                            # (Q, 1) log-decay
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = si <= ti
    # inclusive L_t = sum_{s<=t} da_s as a masked row sum: the TPU lowering
    # has no cumsum, and an MXU product would round the f32 decays
    cum = jnp.sum(jnp.where(tri, da.reshape(1, Q), 0.0), axis=1,
                  keepdims=True)                 # (Q, 1)

    # intra-chunk quadratic form: m[t,s] = (C_t.B_s) exp(L_t - L_s) dt_s, s<=t
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())))  # (Q, Q)
    seg = cum - cum.reshape(1, Q)                # L_t - L_s
    m = jnp.where(tri, cb * jnp.exp(seg) * dt.reshape(1, Q), 0.0)
    y_intra = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())))   # (Q, P)

    # inter-chunk: y[t] += C_t . (exp(L_t) * h_prev)   h_prev: (P, N)
    h_prev = h_scr[...]
    ch = jax.lax.dot_general(cmat, h_prev, (((1,), (1,)), ((), ())))  # (Q, P)
    y_inter = ch * jnp.exp(cum)                  # broadcast (Q,1)

    y_ref[0, 0, :, :] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = exp(L_Q) h_prev + sum_s exp(L_Q - L_s) dt_s x_s B_s^T
    tail = jnp.exp(cum[Q - 1, 0] - cum) * dt     # (Q, 1)
    xw = x * tail                                # (Q, P)
    hc = jax.lax.dot_general(xw, bmat, (((0,), (0,)), ((), ())))    # (P, N)
    h_new = h_prev * jnp.exp(cum[Q - 1, 0]) + hc
    h_scr[...] = h_new

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0, :, :] = h_new


def ssd_scan(
    x: jnp.ndarray,       # (B, S, H, P)
    dt: jnp.ndarray,      # (B, S, H)
    A: jnp.ndarray,       # (H,) negative
    Bm: jnp.ndarray,      # (B, S, N)   group-shared across heads
    Cm: jnp.ndarray,      # (B, S, N)
    D: Optional[jnp.ndarray] = None,   # (H,) skip
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan. Returns (y (B,S,H,P), final state (B,H,P,N))."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # pad dt with zeros -> exp(0 * A) = 1 decay, zero input contribution
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    xt = x.transpose(0, 2, 1, 3)                       # (B, H, S, P)
    dtt = dt.transpose(0, 2, 1)[..., None]             # (B, H, S, 1)
    af = A.astype(jnp.float32).reshape(1, H, 1, 1)
    af = jnp.broadcast_to(af, (1, H, 1, 1))

    kernel = functools.partial(_ssd_kernel, Q=Q, P=P, N=N, nc=nc)

    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, c: (0, h, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, af, Bm, Cm)

    y = y.transpose(0, 2, 1, 3)[:, :S]                 # (B, S, H, P)
    if D is not None:
        y = (y.astype(jnp.float32)
             + D.astype(jnp.float32)[None, None, :, None]
             * x[:, :S].astype(jnp.float32)).astype(y.dtype)
    return y, h
