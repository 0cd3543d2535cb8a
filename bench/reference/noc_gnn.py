# Frozen copy of src/repro/core/noc_gnn.py (commit eba02b4), part of the
# benchmark's plain reference: it imports nothing of the program, so a
# change to the program cannot move the yardstick. Edits from the
# original are marked "bench reference:".
"""GNN-based NoC congestion model (paper §VI-C, Eq. 5-6), pure JAX.

Input: the core-topology graph from the Workload Compiler — nodes = routers
(feature: packet injection rate), directed edges = physical links (feature:
transmission volume in flits, link bandwidth). Message passing runs on BOTH
the graph and its reverse (upstream contention + downstream backpressure,
after Noception [30]) for T iterations; the congestion head predicts each
link's average channel waiting time:

    y_e = MLP(concat(h_u^T, h_v^T, h_e^0))                      (Eq. 5)
    t(k) = k + sum_{l in route} y_l                             (Eq. 6)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.compiler import ChunkGraph, _xy_route
from bench.reference.design_space import WSCDesign

HIDDEN = 32
T_ITERS = 3
NODE_F = 3      # injection rate, out-degree, in-degree
EDGE_F = 3      # log flits, bandwidth (norm), flows


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _mlp(params, x):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def gnn_logits(params: Dict, node_x: jnp.ndarray, edge_x: jnp.ndarray,
               senders: jnp.ndarray, receivers: jnp.ndarray,
               n_nodes: int,
               edge_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Raw head output = predicted log1p(waiting time) per edge — the model
    regresses in log space, which conditions training across the 4-decade
    range of waiting times. `edge_mask` (1.0 = real edge, 0.0 = padding)
    zeroes padded edges' messages before the segment sums so padded graphs
    (LinkGraphBatch) aggregate exactly like their unpadded originals."""
    h_v = _mlp(params["node_enc"], node_x)
    h_e0 = _mlp(params["edge_enc"], edge_x)
    h_e = h_e0
    for _ in range(T_ITERS):
        m_in = _mlp(params["msg_fwd"],
                    jnp.concatenate([h_v[senders], h_e], axis=-1))
        m_out = _mlp(params["msg_bwd"],
                     jnp.concatenate([h_v[receivers], h_e], axis=-1))
        if edge_mask is not None:
            m_in = m_in * edge_mask[:, None]
            m_out = m_out * edge_mask[:, None]
        agg_in = jax.ops.segment_sum(m_in, receivers, n_nodes)
        agg_out = jax.ops.segment_sum(m_out, senders, n_nodes)
        h_v = _mlp(params["update"],
                   jnp.concatenate([h_v, agg_in, agg_out], axis=-1))
    y = _mlp(params["head"],
             jnp.concatenate([h_v[senders], h_v[receivers], h_e0], axis=-1))
    return y[:, 0]


def gnn_forward(params: Dict, node_x: jnp.ndarray, edge_x: jnp.ndarray,
                senders: jnp.ndarray, receivers: jnp.ndarray,
                n_nodes: int,
                edge_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Predicted average waiting time per edge (>= 0), Eq. 5. The log-space
    head is clipped at 30 (~1e13 cycles) so an out-of-distribution input
    can't overflow expm1 into inf/NaN downstream."""
    z = gnn_logits(params, node_x, edge_x, senders, receivers, n_nodes,
                   edge_mask)
    return jnp.expm1(jnp.clip(jax.nn.relu(z), 0.0, 30.0))


# ---------------------------------------------------------------------------
# graph featurization from a compiled chunk
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# padded struct-of-arrays batching (DESIGN.md §4b)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LinkGraphBatch:
    """G link graphs padded to a common (n_nodes, n_edges) shape. Padded
    edges carry zero features, point at node 0, and are masked out of the
    message-passing aggregations (`edge_mask`); padded node rows are inert
    because no unmasked edge references them."""
    node_x: np.ndarray      # (G, n_nodes, NODE_F) float32
    edge_x: np.ndarray      # (G, n_edges, EDGE_F) float32
    senders: np.ndarray     # (G, n_edges) int32, padding -> 0
    receivers: np.ndarray   # (G, n_edges) int32, padding -> 0
    edge_mask: np.ndarray   # (G, n_edges) float32, 1 = real edge
    n_nodes: int            # static padded node count
    n_edges_real: np.ndarray  # (G,) real edge count per graph
    target: Optional[np.ndarray] = None   # (G, n_edges), 0 on padding

    def __len__(self) -> int:
        return self.node_x.shape[0]


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


_gnn_forward_jit = jax.jit(gnn_forward, static_argnums=(5,))


# bench reference: the per-graph forward that replaces the padded, vmapped
# `gnn_forward_batch` of the program. Every graph of a bucket runs alone,
# unpadded, in float32 at the highest matmul precision on the host CPU.
_forward_one_jit = jax.jit(gnn_forward, static_argnums=(5,))


#: the type the reference computes in; the correctness control sets
#: bfloat16 (weights, features and arithmetic) to show the comparison fails
DTYPE = jnp.float32


def gnn_forward_per_graph(params: Dict, batch: "LinkGraphBatch") -> np.ndarray:
    cpu = jax.devices("cpu")[0]
    out = []
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.asarray(np.asarray(a), DTYPE), params)
        for f in range(batch.node_x.shape[0]):
            e = int(batch.n_edges_real[f])
            w = np.zeros(batch.edge_x.shape[1], np.float32)
            w[:e] = np.asarray(_forward_one_jit(
                p, jnp.asarray(batch.node_x[f], DTYPE),
                jnp.asarray(batch.edge_x[f, :e], DTYPE),
                jnp.asarray(batch.senders[f, :e]),
                jnp.asarray(batch.receivers[f, :e]), int(batch.n_nodes)),
                np.float32)
            out.append(w)
    return np.stack(out)
