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

from repro import telemetry as tm
from repro.core.compiler import ChunkGraph, _xy_route
from repro.core.design_space import WSCDesign
from repro.core.noc_sim import packets_for_transfer, simulate

HIDDEN = 32
T_ITERS = 3
NODE_F = 3      # injection rate, out-degree, in-degree
EDGE_F = 3      # log flits, bandwidth (norm), flows


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def _mlp_init(key, sizes):
    ks = jax.random.split(key, len(sizes) - 1)
    return [{"w": jax.random.normal(k, (a, b)) * (2.0 / a) ** 0.5,
             "b": jnp.zeros(b)}
            for k, a, b in zip(ks, sizes[:-1], sizes[1:])]


def _mlp(params, x):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def init_gnn(key) -> Dict:
    ks = jax.random.split(key, 6)
    return {
        "node_enc": _mlp_init(ks[0], (NODE_F, HIDDEN, HIDDEN)),
        "edge_enc": _mlp_init(ks[1], (EDGE_F, HIDDEN, HIDDEN)),
        "msg_fwd": _mlp_init(ks[2], (2 * HIDDEN, HIDDEN)),
        "msg_bwd": _mlp_init(ks[3], (2 * HIDDEN, HIDDEN)),
        "update": _mlp_init(ks[4], (3 * HIDDEN, HIDDEN, HIDDEN)),
        "head": _mlp_init(ks[5], (3 * HIDDEN, HIDDEN, 1)),
    }


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


@dataclasses.dataclass
class LinkGraph:
    node_x: np.ndarray
    edge_x: np.ndarray
    senders: np.ndarray
    receivers: np.ndarray
    links: List[Tuple[int, int]]
    n_nodes: int
    target: np.ndarray = None     # per-edge avg wait (from noc_sim)


def featurize_transfer(graph: ChunkGraph, design: WSCDesign, t_idx: int,
                       with_target: bool = False) -> LinkGraph:
    W = graph.array[1]
    n = graph.n_cores
    pkts = packets_for_transfer(graph, design, t_idx)

    link_flits: Dict[Tuple[int, int], float] = {}
    link_flows: Dict[Tuple[int, int], int] = {}
    inj = np.zeros(n)
    for p in pkts:
        inj[p.src] += p.flits
        for hop in _xy_route(p.src, p.dst, W):
            link_flits[hop] = link_flits.get(hop, 0.0) + p.flits
            link_flows[hop] = link_flows.get(hop, 0) + 1
    links = sorted(link_flits)
    senders = np.array([u for u, _ in links], np.int32)
    receivers = np.array([v for _, v in links], np.int32)

    dur = max(graph.ops[graph.transfers[t_idx].src_op].tile.cycles, 1.0)
    out_deg = np.zeros(n)
    in_deg = np.zeros(n)
    for u, v in links:
        out_deg[u] += 1
        in_deg[v] += 1
    node_x = np.stack([inj / dur, out_deg / 4.0, in_deg / 4.0], axis=1)
    edge_x = np.stack([
        np.log1p([link_flits[l] for l in links]),
        np.full(len(links), design.noc_bw / 4096.0),
        np.log1p([link_flows[l] for l in links]),
    ], axis=1)

    target = None
    if with_target:
        res = simulate(pkts, W)
        target = np.array([res.link_wait.get(l, 0.0) for l in links])
    return LinkGraph(node_x.astype(np.float32), edge_x.astype(np.float32),
                     senders, receivers, links, n, target)


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


def pad_link_graphs(graphs: Sequence[LinkGraph],
                    n_nodes: Optional[int] = None,
                    n_edges: Optional[int] = None,
                    with_target: bool = False) -> LinkGraphBatch:
    """Stack LinkGraphs into one padded batch. Node/edge capacities default
    to the next power of two above the max in the batch, so repeated calls
    bucket onto a handful of jit-compiled shapes."""
    G = len(graphs)
    nn = n_nodes or next_pow2(max((g.n_nodes for g in graphs), default=1))
    ne = n_edges or next_pow2(max((len(g.links) for g in graphs), default=1))
    node_x = np.zeros((G, nn, NODE_F), np.float32)
    edge_x = np.zeros((G, ne, EDGE_F), np.float32)
    senders = np.zeros((G, ne), np.int32)
    receivers = np.zeros((G, ne), np.int32)
    mask = np.zeros((G, ne), np.float32)
    n_real = np.zeros(G, np.int64)
    target = np.zeros((G, ne), np.float32) if with_target else None
    for i, g in enumerate(graphs):
        e = len(g.links)
        n_real[i] = e
        node_x[i, :g.n_nodes] = g.node_x
        edge_x[i, :e] = g.edge_x
        senders[i, :e] = g.senders
        receivers[i, :e] = g.receivers
        mask[i, :e] = 1.0
        if with_target and g.target is not None:
            target[i, :e] = g.target
    return LinkGraphBatch(node_x, edge_x, senders, receivers, mask, nn,
                          n_real, target)


@functools.partial(jax.jit, static_argnames=("n_nodes",))
@jax.named_scope("evaluate.gnn.forward")
def _forward_batch_jit(params, node_x, edge_x, senders, receivers, edge_mask,
                       *, n_nodes):
    def one(nx, ex, s, r, m):
        return gnn_forward(params, nx, ex, s, r, n_nodes, edge_mask=m)
    return jax.vmap(one)(node_x, edge_x, senders, receivers, edge_mask)


def gnn_forward_batch(params: Dict, batch: LinkGraphBatch) -> np.ndarray:
    """Predicted waiting time for every edge of every graph in one XLA call.
    Returns (G, n_edges) float32; padded positions are meaningless."""
    out = _forward_batch_jit(
        jax.tree.map(jnp.asarray, params), jnp.asarray(batch.node_x),
        jnp.asarray(batch.edge_x), jnp.asarray(batch.senders),
        jnp.asarray(batch.receivers), jnp.asarray(batch.edge_mask),
        n_nodes=int(batch.n_nodes))
    return tm.to_host(out, "gnn_out")


@functools.partial(jax.jit, static_argnames=("n_nodes",))
def _val_batch_jit(params, node_x, edge_x, senders, receivers, edge_mask,
                   target, *, n_nodes):
    def one(nx, ex, s, r, m, tgt):
        z = gnn_logits(params, nx, ex, s, r, n_nodes, edge_mask=m)
        err = ((z - jnp.log1p(tgt)) ** 2) * m
        return jnp.sum(err) / jnp.maximum(jnp.sum(m), 1.0), z
    return jax.vmap(one)(node_x, edge_x, senders, receivers, edge_mask,
                         target)


def kendall_tau(a: np.ndarray, b: np.ndarray, max_n: int = 2000,
                seed: int = 0) -> float:
    """Kendall rank correlation, vectorized over all O(n^2) pairs (with a
    deterministic subsample above `max_n` elements)."""
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    n = len(a)
    if n < 2:
        return 0.0
    if n > max_n:
        idx = np.random.default_rng(seed).choice(n, max_n, replace=False)
        a, b = a[idx], b[idx]
        n = max_n
    iu = np.triu_indices(n, 1)
    sa = np.sign(a[:, None] - a[None, :])[iu]
    sb = np.sign(b[:, None] - b[None, :])[iu]
    m = (sa != 0) & (sb != 0)
    den = int(m.sum())
    num = int(((sa == sb) & m).sum()) - int(((sa != sb) & m).sum())
    return num / max(den, 1)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainHistory:
    """Per-epoch training record. `train_loss` is the averaged per-graph
    log-space MSE (the quantity the old API returned as a bare list);
    `val_loss` / `val_kendall_tau` are held-out metrics (empty when
    val_frac == 0). `best_epoch` indexes the epoch whose parameters were
    returned; `stopped_epoch` is set when early stopping fired."""
    train_loss: List[float] = dataclasses.field(default_factory=list)
    val_loss: List[float] = dataclasses.field(default_factory=list)
    val_kendall_tau: List[float] = dataclasses.field(default_factory=list)
    best_epoch: int = -1
    stopped_epoch: Optional[int] = None

    @property
    def best_val_loss(self) -> Optional[float]:
        """Validation loss of the epoch whose parameters were returned —
        NOT the last epoch's (early stopping returns the best checkpoint,
        so the stagnant tail's metrics would misstate its quality)."""
        return self.val_loss[self.best_epoch] \
            if self.val_loss and self.best_epoch >= 0 else None

    @property
    def best_val_kendall_tau(self) -> Optional[float]:
        return self.val_kendall_tau[self.best_epoch] \
            if self.val_kendall_tau and self.best_epoch >= 0 else None


def _val_metrics(params: Dict, batch: LinkGraphBatch) -> Tuple[float, float]:
    losses, zs = _val_batch_jit(
        jax.tree.map(jnp.asarray, params), jnp.asarray(batch.node_x),
        jnp.asarray(batch.edge_x), jnp.asarray(batch.senders),
        jnp.asarray(batch.receivers), jnp.asarray(batch.edge_mask),
        jnp.asarray(batch.target), n_nodes=int(batch.n_nodes))
    losses, zs = tm.to_host((losses, zs), "gnn_val")
    real = np.asarray(batch.edge_mask) > 0
    # rank what the deployed predictor actually outputs: gnn_forward applies
    # expm1(clip(relu(z))), so negative logits collapse to tied zero waits —
    # ranking raw z would credit orderings the model cannot express
    pred = np.clip(np.maximum(np.asarray(zs), 0.0), 0.0, 30.0)
    kt = kendall_tau(pred[real], np.asarray(batch.target)[real])
    return float(np.mean(np.asarray(losses))), kt


@functools.partial(jax.jit, static_argnames=("n_nodes",))
@jax.named_scope("calibrate.train")
def _train_step_jit(params, m, v, step, lr, node_x, edge_x, senders,
                    receivers, edge_mask, target, *, n_nodes):
    """One fused (grad + Adam) update on a padded graph: masked-mean MSE in
    log space equals the unpadded per-graph mean, so bucketing graphs to
    pow2 shapes changes the compile count, not the optimization problem."""
    def loss_fn(p):
        z = gnn_logits(p, node_x, edge_x, senders, receivers, n_nodes,
                       edge_mask=edge_mask)
        err = ((z - jnp.log1p(target)) ** 2) * edge_mask
        return jnp.sum(err) / jnp.maximum(jnp.sum(edge_mask), 1.0)

    lval, grads = jax.value_and_grad(loss_fn)(params)
    b1, b2 = 0.9, 0.999
    m = jax.tree.map(lambda a, g_: b1 * a + (1 - b1) * g_, m, grads)
    v = jax.tree.map(lambda a, g_: b2 * a + (1 - b2) * g_ * g_, v, grads)
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    params = jax.tree.map(
        lambda p_, m_, v_: p_ - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + 1e-8),
        params, m, v)
    return params, m, v, lval


def train_gnn(params: Dict, dataset: List[LinkGraph], epochs: int = 60,
              lr: float = 3e-3, seed: int = 0, val_frac: float = 0.0,
              patience: Optional[int] = None) -> Tuple[Dict, TrainHistory]:
    """Full-batch-per-graph Adam on log1p(wait) MSE.

    With `val_frac` > 0 a deterministic held-out split is scored every epoch
    (log-space MSE + Kendall tau of predicted vs simulated waits); with
    `patience` set, training stops after that many epochs without val-loss
    improvement and the best-epoch parameters are returned — the signal the
    online calibration loop (calibration.py) early-stops on.
    """

    rng = np.random.default_rng(seed)

    usable = [g for g in dataset
              if g.target is not None and len(g.links) > 0]
    val: List[LinkGraph] = []
    train = list(dataset)
    if val_frac > 0.0 and len(usable) >= 2:
        n_val = max(1, int(round(val_frac * len(usable))))
        n_val = min(n_val, len(usable) - 1)
        picked = rng.permutation(len(usable))[:n_val]
        val = [usable[i] for i in picked]
        val_ids = {id(g) for g in val}
        train = [g for g in dataset if id(g) not in val_ids]
    val_batch = pad_link_graphs(val, with_target=True) if val else None

    # shape-bucketed fused train step: each graph is padded to pow2
    # node/edge capacities (masked-mean loss == the unpadded mean), and the
    # grad + Adam update runs as ONE jitted call per bucket — a handful of
    # compiles total instead of one per distinct graph shape, and none of
    # the per-step eager tree.map dispatch overhead
    padded = {}
    for g in dataset:
        if g.target is None or len(g.links) == 0:
            padded[id(g)] = None
            continue
        nn = next_pow2(g.n_nodes)
        ne = next_pow2(len(g.links))
        b = pad_link_graphs([g], n_nodes=nn, n_edges=ne, with_target=True)
        padded[id(g)] = (jnp.asarray(b.node_x[0]), jnp.asarray(b.edge_x[0]),
                         jnp.asarray(b.senders[0]), jnp.asarray(b.receivers[0]),
                         jnp.asarray(b.edge_mask[0]),
                         jnp.asarray(b.target[0]), nn)

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    hist = TrainHistory()
    best_params = params
    best_val = float("inf")
    since_best = 0
    step = 0
    for ep in range(epochs):
        order = rng.permutation(len(train))
        lvals = []
        for gi in order:
            arrs = padded.get(id(train[gi]))
            if arrs is None:
                continue
            step += 1
            params, m, v, lval = _train_step_jit(
                params, m, v, jnp.asarray(float(step)),
                jnp.asarray(lr, jnp.float32), *arrs[:6], n_nodes=arrs[6])
            lvals.append(lval)
        # one read of the epoch's losses, summed in step order
        ep_loss = 0.0
        for lval in tm.to_host(lvals, "gnn_train_loss"):
            ep_loss += float(lval)
        hist.train_loss.append(ep_loss / max(len(train), 1))
        if val_batch is not None:
            vl, kt = _val_metrics(params, val_batch)
            hist.val_loss.append(vl)
            hist.val_kendall_tau.append(kt)
            if vl < best_val - 1e-12:
                best_val, best_params, since_best = vl, params, 0
                hist.best_epoch = ep
            else:
                since_best += 1
                if patience is not None and since_best >= patience:
                    hist.stopped_epoch = ep
                    return best_params, hist
    if val_batch is not None:
        return best_params, hist
    hist.best_epoch = epochs - 1
    return params, hist


_gnn_forward_jit = jax.jit(gnn_forward, static_argnums=(5,))


def predict_transfer_makespan(params: Dict, graph: ChunkGraph,
                              design: WSCDesign, t_idx: int) -> float:
    """Eq. 6 reconstruction: per-packet t(k) = k + sum of predicted waits on
    its route; transfer makespan = max over packets of inject + latency."""
    g = featurize_transfer(graph, design, t_idx)
    if len(g.links) == 0:
        return 0.0
    wait = np.asarray(_gnn_forward_jit(
        jax.tree.map(jnp.asarray, params), jnp.asarray(g.node_x),
        jnp.asarray(g.edge_x), jnp.asarray(g.senders),
        jnp.asarray(g.receivers), int(g.n_nodes)))
    wait_by_link = {l: float(w) for l, w in zip(g.links, wait)}
    W = graph.array[1]
    pkts = packets_for_transfer(graph, design, t_idx)
    worst = 0.0
    for p in pkts:
        route = _xy_route(p.src, p.dst, W)
        t = p.flits + len(route) + sum(wait_by_link.get(h, 0.0) for h in route)
        worst = max(worst, p.inject + t)
    return worst


def chunk_latency_cycles_gnn(params: Dict, graph: ChunkGraph,
                             design: WSCDesign) -> float:
    total = 0.0
    for i, node in enumerate(graph.ops):
        total += node.tile.cycles
        if i < len(graph.transfers) and graph.transfers[i].pairs:
            total += predict_transfer_makespan(params, graph, design, i)
    return total
