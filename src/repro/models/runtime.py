"""Runtime options threaded through every model forward pass."""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Runtime:
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    use_pallas: bool = False        # flash-attn / SSD Pallas kernels (TPU)
    interpret: bool = False         # Pallas interpret mode (CPU validation)
    remat: str = "block"            # none | block  (checkpoint each layer)
    ring_cache: bool = False        # windowed layers use ring-buffer KV caches
    ssd_chunk: int = 128
    # sharding constraint (PartitionSpec) for the MoE dispatch buffer
    # (E, C, D); prevents SPMD from replicating the capacity buffer. Set by
    # the launcher; None on single-device CPU runs.
    moe_buf_spec: Any = None
    # mesh axis sizes, e.g. {"pod": 2, "data": 16, "model": 16}; enables
    # divisibility-aware attention activation constraints (head-parallel when
    # heads divide the model axis, sequence-parallel otherwise). None = no
    # constraints (single-device runs).
    mesh_axes: Any = None
    # decode cache update via dynamic-update-slice (keeps sequence-sharded
    # caches sharded under SPMD). False reproduces the scatter baseline.
    opt_cache_dus: bool = True
    # SSD head-dim tensor parallelism (False reproduces the naive flat-TP
    # baseline that reshards the packed in_proj output every layer)
    opt_ssm_head_tp: bool = True
    # long-prefill attention computes scores from bf16 operands with fp32
    # MXU accumulation instead of materializing fp32 copies of Q/K/V
    # (halves the prefill score traffic; numerics validated in tests)
    opt_bf16_scores: bool = False
    # gradient-accumulation dtype for microbatched training (fp32 default;
    # bf16 halves the per-microbatch reduction bytes)
    grad_acc_dtype: Any = jnp.float32


CPU_TEST = Runtime(compute_dtype=jnp.float32, remat="none")


def kernel_shard_axes(rt: Runtime, batch: int, heads: Tuple[int, ...]
                      ) -> Tuple[Optional[Tuple[str, ...]], Optional[str]]:
    """Mesh axes a Pallas kernel call is split over under `shard_map`
    (GSPMD cannot partition a Mosaic kernel): the batch over the data axes
    when it divides them, and the heads over `model` when every head count
    in `heads` divides it; otherwise that dim is replicated."""
    axes = rt.mesh_axes
    dp = tuple(a for a in ("pod", "data") if axes.get(a, 1) > 1)
    dp_size = math.prod(axes[a] for a in dp)
    model = axes.get("model", 1)
    batch_axes = dp if dp and batch % dp_size == 0 else None
    head_axis = ("model" if model > 1 and all(h % model == 0 for h in heads)
                 else None)
    return batch_axes, head_axis
