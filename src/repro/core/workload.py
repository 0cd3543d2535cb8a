"""LLM workload descriptors for the DSE (paper §VIII-A, Table II) + bridge
from the runtime's ModelConfig so every assigned architecture is a DSE
benchmark too.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig

BYTES = 2          # bf16 activations/weights on-wafer


@dataclasses.dataclass(frozen=True)
class GEMMOp:
    name: str
    M: int            # tokens (rows)
    K: int
    N: int
    weight: bool = True          # K x N is a resident weight (vs act x act)

    def flops(self) -> float:
        return 2.0 * self.M * self.K * self.N

    def in_bytes(self) -> float:
        return (self.M * self.K + self.K * self.N) * BYTES

    def out_bytes(self) -> float:
        return self.M * self.N * BYTES


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One row of a workload's table of layer kinds: `count` layers that
    share one GEMM list. `moe` marks routed-expert layers (router, routed
    and shared SwiGLU experts), `mtp` the multi-token-prediction block
    (its `eh_proj`, then an attention and an MoE block)."""
    name: str
    count: int
    moe: bool = False
    mtp: bool = False


@dataclasses.dataclass(frozen=True)
class LLMWorkload:
    """A modelled LLM job. The paper's rows and every `from_model_config`
    workload are one uniform layer of six GEMMs (`uniform`). Setting any
    of the fields after `gpu_budget` makes a table of layer kinds
    (`layer_kinds`): leading dense layers before routed-expert layers
    (`n_dense_layers`, expert width `d_ff_expert`, `moe_shared` shared
    experts), latent attention (MLA: `q_lora_rank`, `kv_lora_rank`, q/k
    heads of `qk_nope_head_dim + qk_rope_head_dim`, v heads of
    `v_head_dim`) and `mtp_layers` multi-token-prediction blocks, which
    run in training only. A table of layer kinds models MLA attention, so
    it needs `q_lora_rank` and `kv_lora_rank`.

    Departures from the published models, as for the uniform layer:
    norms, RoPE, the router's softmax/sigmoid and the LM head are left out
    of the per-layer GEMMs; routing is not node-limited, so an expert
    all-to-all carries every one of the top-k copies."""
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    seq: int
    batch: int
    phase: str                     # train | prefill | decode
    moe_experts: int = 0
    moe_topk: int = 0
    gpu_budget: int = 1            # baseline GPU count (area matching)
    n_dense_layers: int = 0        # leading dense layers (width d_ff)
    d_ff_expert: int = 0           # routed / shared expert width
    moe_shared: int = 0            # shared experts per MoE layer
    q_lora_rank: int = 0           # MLA query compression
    kv_lora_rank: int = 0          # MLA latent cache width
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mtp_layers: int = 0            # multi-token-prediction blocks (train)

    def __post_init__(self):
        if not self.uniform and not (self.q_lora_rank
                                     and self.kv_lora_rank):
            raise ValueError(
                f"workload {self.name!r}: a table of layer kinds models MLA "
                f"attention and needs q_lora_rank and kv_lora_rank")

    # ------------------------------------------------------------------

    @property
    def uniform(self) -> bool:
        """True for the one-kind table built from the first fields: every
        layer the same six GEMMs, as the paper's rows."""
        return not (self.n_dense_layers or self.d_ff_expert
                    or self.moe_shared or self.q_lora_rank
                    or self.kv_lora_rank or self.mtp_layers)

    def layer_kinds(self) -> Tuple[LayerKind, ...]:
        """The table of layer kinds in published order. A uniform workload
        is the one kind "layer"; otherwise "dense" (the leading dense
        layers), "moe" (the rest, when there are routed experts) and, in
        training, "mtp"."""
        if self.uniform:
            return (LayerKind("layer", self.n_layers,
                              moe=bool(self.moe_experts)),)
        n_dense = self.n_dense_layers if self.moe_experts else self.n_layers
        kinds = []
        if n_dense:
            kinds.append(LayerKind("dense", n_dense))
        if self.n_layers - n_dense:
            kinds.append(LayerKind("moe", self.n_layers - n_dense, moe=True))
        if self.mtp_layers and self.phase == "train":
            kinds.append(LayerKind("mtp", self.mtp_layers,
                                   moe=bool(self.moe_experts), mtp=True))
        return tuple(kinds)

    def require_uniform(self, reader: str) -> None:
        """Refuse a table of layer kinds where `reader` models one uniform
        layer: a silent collapse to one layer would score another job."""
        if not self.uniform:
            kinds = ", ".join(f"{k.count} {k.name}"
                              for k in self.layer_kinds())
            raise ValueError(
                f"workload {self.name!r} has layer kinds ({kinds}); "
                f"{reader} models one uniform layer and cannot score it")

    def stage_kind_counts(self, pp: int) -> np.ndarray:
        """(pp, n_kinds) layers of each kind per pipeline stage: a balanced
        contiguous split of the `n_layers` in published order (stage s
        holds layers floor(s L / pp) .. floor((s+1) L / pp) - 1), the MTP
        blocks in the last stage beside the head."""
        kinds = self.layer_kinds()
        pp = max(int(pp), 1)
        kind_of = np.repeat(np.arange(len(kinds)),
                            [k.count if not k.mtp else 0 for k in kinds])
        L = len(kind_of)
        out = np.zeros((pp, len(kinds)), np.int64)
        for s in range(pp):
            lo, hi = s * L // pp, (s + 1) * L // pp
            out[s] = np.bincount(kind_of[lo:hi], minlength=len(kinds))
        for i, k in enumerate(kinds):
            if k.mtp:
                out[-1, i] += k.count
        return out

    def _attn_params(self) -> int:
        D, H = self.d_model, max(self.n_heads, 1)
        c, r, ql = self.kv_lora_rank, self.qk_rope_head_dim, self.q_lora_rank
        hq = self.qk_nope_head_dim + r
        return (D * ql + ql * H * hq + D * (c + r) + c * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * D)

    def _kind_params(self, k: LayerKind, active: bool) -> int:
        D = self.d_model
        p = self._attn_params() + (2 * D * D if k.mtp else 0)
        if not k.moe:
            return p + 3 * D * self.d_ff
        fe = self.d_ff_expert or self.d_ff
        routed = self.moe_topk if active else self.moe_experts
        return (p + D * self.moe_experts
                + (routed + self.moe_shared) * 3 * D * fe)

    def params_bytes(self) -> float:
        D, F, L = self.d_model, self.d_ff, self.n_layers
        if not self.uniform:
            return (sum(k.count * self._kind_params(k, False)
                        for k in self.layer_kinds())
                    + 2 * self.vocab * D) * BYTES
        per = 4 * D * D + 3 * D * F * max(self.moe_experts, 1)
        return (L * per + 2 * self.vocab * D) * BYTES

    def expert_params_bytes(self) -> float:
        """Bytes of MoE routed-expert weights (the `ep`-shardable slice of
        `params_bytes`; shared experts and the router are replicated); 0
        for dense models."""
        if not self.moe_experts:
            return 0.0
        D, F, L = self.d_model, self.d_ff, self.n_layers
        if not self.uniform:
            fe = self.d_ff_expert or F
            n_moe = sum(k.count for k in self.layer_kinds() if k.moe)
            return n_moe * 3 * D * fe * self.moe_experts * BYTES
        return L * 3 * D * F * self.moe_experts * BYTES

    def active_params(self) -> float:
        D, F, L = self.d_model, self.d_ff, self.n_layers
        if not self.uniform:
            return (sum(k.count * self._kind_params(k, True)
                        for k in self.layer_kinds()) + self.vocab * D)
        e = self.moe_topk if self.moe_experts else 1
        return L * (4 * D * D + 3 * D * F * e) + self.vocab * D

    def tokens_per_step(self) -> int:
        if self.phase == "decode":
            return self.batch
        return self.batch * self.seq

    def layer_ops(self, tp: int = 1, mb_tokens: Optional[int] = None,
                  kind: Optional[LayerKind] = None) -> List[GEMMOp]:
        """One layer's GEMMs under tensor parallelism `tp` (Megatron split:
        heads/ffn sharded; two collectives per layer accounted by chunk_eval).
        M = tokens per microbatch. A table of layer kinds needs `kind`
        (`kind_ops`); the uniform layer takes none."""
        if not self.uniform:
            if kind is None:
                self.require_uniform("layer_ops without a kind")
            M = mb_tokens if mb_tokens is not None else self.tokens_per_step()
            return [GEMMOp(n, int(m), int(k), int(nn), w)
                    for n, m, k, nn, w in self.kind_ops(kind, tp, M)]
        D, F = self.d_model, self.d_ff
        hd = D // max(self.n_heads, 1)
        M = mb_tokens if mb_tokens is not None else self.tokens_per_step()
        # Attention context length is the full sequence in every phase:
        # decode reads the whole KV cache, and a prefill/train token attends
        # over its prompt no matter how the M tokens are sharded across
        # dp/microbatch splits (M // batch would shrink the KV with the
        # split, underestimating scores/attnv FLOPs and traffic).
        kv_len = self.seq
        e = self.moe_topk if self.moe_experts else 1
        ops = [
            GEMMOp("qkv", M, D, (self.n_heads + 2 * self.n_kv) * hd // tp),
            GEMMOp("scores", M * max(self.n_heads // tp, 1) // max(self.n_heads, 1),
                   hd, kv_len, weight=False),
            GEMMOp("attnv", M * max(self.n_heads // tp, 1) // max(self.n_heads, 1),
                   kv_len, hd, weight=False),
            GEMMOp("attn_out", M, self.n_heads * hd // tp, D),
            GEMMOp("mlp_in", M * e, D, 2 * F // tp),
            GEMMOp("mlp_out", M * e, F // tp, D),
        ]
        return ops

    def layer_ops_batch(self, tp, mb_tokens):
        """Vectorized `layer_ops`: `tp`/`mb_tokens` are (C,) int arrays, the
        result is a dict of (n_ops, C) int arrays M/K/N plus the static
        `weight` flags — column c reproduces layer_ops(tp[c], mb_tokens[c])
        exactly (integer semantics included). Uniform workloads only: a
        table of layer kinds is read per kind (`kind_ops`)."""
        self.require_uniform("layer_ops_batch")
        tp = np.asarray(tp, np.int64)
        M = np.asarray(mb_tokens, np.int64)
        D, F = self.d_model, self.d_ff
        hd = D // max(self.n_heads, 1)
        kv_len = np.full_like(M, self.seq)   # full context in every phase
        e = self.moe_topk if self.moe_experts else 1
        heads_tp = np.maximum(self.n_heads // tp, 1)
        m_attn = M * heads_tp // max(self.n_heads, 1)
        zeros = np.zeros_like(M)
        Ms = np.stack([M, m_attn, m_attn, M, M * e, M * e])
        Ks = np.stack([zeros + D, zeros + hd, kv_len,
                       self.n_heads * hd // tp, zeros + D, F // tp])
        Ns = np.stack([(self.n_heads + 2 * self.n_kv) * hd // tp, kv_len,
                       zeros + hd, zeros + D, 2 * F // tp, zeros + D])
        weight = (True, False, False, True, True, True)
        names = ("qkv", "scores", "attnv", "attn_out", "mlp_in", "mlp_out")
        return {"M": Ms, "K": Ks, "N": Ns, "weight": weight, "names": names}

    def kind_ops(self, kind: LayerKind, tp, M, xp=np):
        """One layer of `kind` as (name, M, K, N, weight) GEMMs under `tp`,
        M the microbatch's tokens. `tp` and `M` are ints or int arrays of
        one shape, and `xp` the array module (NumPy, or `jax.numpy` inside
        the compiled evaluator): integer arithmetic only, so every array
        module gives the same numbers.

        Attention, in every kind, is MLA. Under `tp` the compressions (`q_a`, `kv_a`) are replicated and everything after
        them is split by heads. The per-head GEMMs (scores, attn-v, and in
        decode the absorbed projections) have M x heads rows and a context
        of `seq`. Training and prefill run MLA as published (`kv_b`
        expands the latent into per-head k and v); decode runs the absorbed
        form DeepSeek serves with: q_nope folded into the latent
        (`q_absorb`), scores over the cached latent and rope key
        (kv_lora_rank + qk_rope_head_dim), attn-v over the latent, then
        the per-head up-projection (`v_up`) and `attn_out`. An MoE kind
        adds the router (d_model -> moe_experts), the routed SwiGLU at M x
        moe_topk rows and the shared SwiGLU at M x moe_shared rows; a dense
        kind the SwiGLU of width d_ff. The MTP kind starts with `eh_proj`
        (2 d_model -> d_model, replicated)."""
        D, H = self.d_model, max(self.n_heads, 1)
        M = xp.asarray(M)
        zi = xp.zeros_like(M)
        tp = zi + tp
        m_h = M * xp.maximum(H // tp, 1)       # rows of the per-head GEMMs
        kv = zi + self.seq
        c, r = self.kv_lora_rank, self.qk_rope_head_dim
        nope, v, ql = self.qk_nope_head_dim, self.v_head_dim, self.q_lora_rank
        ops = [("eh_proj", M, zi + 2 * D, zi + D, True)] if kind.mtp else []
        ops += [("q_a", M, zi + D, zi + ql, True),
                ("q_b", M, zi + ql, H * (nope + r) // tp, True),
                ("kv_a", M, zi + D, zi + (c + r), True)]
        if self.phase == "decode":
            ops += [("q_absorb", m_h, zi + nope, zi + c, True),
                    ("scores", m_h, zi + (c + r), kv, False),
                    ("attnv", m_h, kv, zi + c, False),
                    ("v_up", m_h, zi + c, zi + v, True)]
        else:
            ops += [("kv_b", M, zi + c, H * (nope + v) // tp, True),
                    ("scores", m_h, zi + (nope + r), kv, False),
                    ("attnv", m_h, kv, zi + v, False)]
        ops.append(("attn_out", M, H * v // tp, zi + D, True))
        if not kind.moe:
            F = self.d_ff
            return ops + [("mlp_in", M, zi + D, 2 * F // tp, True),
                          ("mlp_out", M, F // tp, zi + D, True)]
        fe = self.d_ff_expert or self.d_ff
        ops += [("router", M, zi + D, zi + self.moe_experts, True),
                ("mlp_in", M * self.moe_topk, zi + D, 2 * fe // tp, True),
                ("mlp_out", M * self.moe_topk, fe // tp, zi + D, True)]
        if self.moe_shared:
            fs = fe * self.moe_shared
            ops += [("shared_in", M, zi + D, 2 * fs // tp, True),
                    ("shared_out", M, fs // tp, zi + D, True)]
        return ops

    def flops_per_step(self) -> float:
        mult = 3.0 if self.phase == "train" else 1.0   # fwd+bwd ~ 3x fwd
        return 2.0 * self.active_params() * self.tokens_per_step() * mult

    def kv_bytes_per_layer(self) -> float:
        """Cache bytes of one layer at (batch, seq): K and V of n_kv heads,
        or MLA's latent and rope key (kv_lora_rank + qk_rope_head_dim
        values a token)."""
        if self.kv_lora_rank:
            return (self.batch * self.seq
                    * (self.kv_lora_rank + self.qk_rope_head_dim) * BYTES)
        hd = self.d_model // max(self.n_heads, 1)
        return 2 * self.batch * self.seq * self.n_kv * hd * BYTES

    def act_bytes_per_layer(self, mb_tokens: int) -> float:
        return mb_tokens * self.d_model * BYTES


# ---------------------------------------------------------------------------
# request-level serving descriptor (ISSUE 4 tentpole; consumed by
# repro.core.serving) — one arrival batch of requests, each a prompt to
# prefill and a number of tokens to decode under continuous batching.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RequestMix:
    """Prompt/output length distribution for one serving arrival batch.

    All requests arrive at t=0 in queue order (matching
    `repro.serve.engine.ServeEngine.run`). Frozen + tuple fields so a mix is
    hashable and can key caches alongside `LLMWorkload`.
    """
    prompt_lens: Tuple[int, ...]
    out_lens: Tuple[int, ...]         # max_new_tokens per request

    def __post_init__(self):
        # coerce to tuples so list inputs keep the hashability contract
        object.__setattr__(self, "prompt_lens", tuple(self.prompt_lens))
        object.__setattr__(self, "out_lens", tuple(self.out_lens))
        if len(self.prompt_lens) != len(self.out_lens):
            raise ValueError("prompt_lens and out_lens must align")
        if not self.prompt_lens:
            raise ValueError("RequestMix needs at least one request")
        if min(self.prompt_lens) < 1 or min(self.out_lens) < 1:
            raise ValueError("prompt/output lengths must be >= 1")

    @property
    def n_requests(self) -> int:
        return len(self.prompt_lens)

    @property
    def mean_prompt(self) -> float:
        return float(np.mean(self.prompt_lens))

    @property
    def mean_out(self) -> float:
        return float(np.mean(self.out_lens))

    def total_out_tokens(self) -> int:
        return int(sum(self.out_lens))

    def context_len(self) -> int:
        """Representative mid-generation context (KV length) for sizing the
        steady-state decode step: prompt plus half the generated tokens."""
        return max(1, int(round(self.mean_prompt + 0.5 * self.mean_out)))

    @classmethod
    def uniform(cls, n_requests: int, prompt_len: int,
                out_len: int) -> "RequestMix":
        return cls((prompt_len,) * n_requests, (out_len,) * n_requests)

    @classmethod
    def sampled(cls, rng: np.random.Generator, n_requests: int,
                prompt_range: Tuple[int, int],
                out_range: Tuple[int, int]) -> "RequestMix":
        p = rng.integers(prompt_range[0], prompt_range[1] + 1, n_requests)
        o = rng.integers(out_range[0], out_range[1] + 1, n_requests)
        return cls(tuple(int(x) for x in p), tuple(int(x) for x in o))

    def as_trace(self, tenant=None):
        """Lift this one-batch mix into the timed-arrival frame: a
        `core.traces.RequestTrace` with every request at step 0 under a
        single tenant — the degenerate case `trace_schedule` reduces to
        `continuous_batch_schedule` on. Lazy import: traces layers on top
        of this module."""
        # traces imports this module
        from repro.core.traces import DEFAULT_TENANT, RequestTrace
        return RequestTrace.from_mix(
            self, DEFAULT_TENANT if tenant is None else tenant)


# ---------------------------------------------------------------------------
# paper Table II benchmarks (Megatron-LM / GPT-3 / ZeRO-Infinity scalings)
# ---------------------------------------------------------------------------

def _gpt(name, params_b, layers, hidden, heads, gpus, batch) -> LLMWorkload:
    return LLMWorkload(
        name=name, n_layers=layers, d_model=hidden, n_heads=heads,
        n_kv=heads, d_ff=4 * hidden, vocab=51200, seq=2048, batch=batch,
        phase="train", gpu_budget=gpus)


GPT_BENCHMARKS: Tuple[LLMWorkload, ...] = (
    _gpt("GPT-1.7B", 1.7, 24, 2304, 24, 32, 512),
    _gpt("GPT-3.6B", 3.6, 30, 3072, 32, 64, 512),
    _gpt("GPT-7.5B", 7.5, 36, 4096, 32, 128, 512),
    _gpt("GPT-18B", 18.4, 40, 6144, 48, 256, 1024),
    _gpt("GPT-39B", 39.1, 48, 8192, 64, 512, 1536),
    _gpt("GPT-76B", 76.1, 60, 10240, 80, 1024, 1792),
    _gpt("GPT-145B", 145.6, 80, 12288, 96, 1536, 2304),
    _gpt("GPT-175B", 175.0, 96, 12288, 96, 1000, 2048),
    _gpt("GPT-310B", 310.1, 96, 16384, 128, 1920, 2160),
    _gpt("GPT-530B", 529.6, 105, 20480, 128, 2520, 2520),
    _gpt("GPT-1T", 1008.0, 128, 25600, 160, 3072, 3072),
    _gpt("GPT-2.2T", 2244.5, 192, 32768, 256, 6000, 3072),
    _gpt("GPT-4T", 4066.6, 192, 43008, 432, 12000, 5500),
    _gpt("GPT-9.6T", 9588.2, 195, 65536, 512, 30000, 10000),
    _gpt("GPT-18T", 18436.5, 240, 81920, 620, 60000, 15000),
    _gpt("GPT-32T", 32405.7, 270, 102400, 850, 100000, 20000),
)


# ---------------------------------------------------------------------------
# workloads with a table of layer kinds, by name
# ---------------------------------------------------------------------------

#: DeepSeek-V3 pre-training. Widths: the published config
#: (huggingface.co/deepseek-ai/DeepSeek-V3, config.json): 61 layers of MLA,
#: the first 3 dense (SwiGLU 18432), then 58 MoE (256 routed experts of
#: width 2048, top-8, 1 shared), one MTP block. The job: arXiv:2412.19437,
#: 4K sequences at the held batch of 15360 (§4.2) on 2048 H800 GPUs
#: (§3.1), the area budget.
DEEPSEEK_V3 = LLMWorkload(
    name="DeepSeek-V3", n_layers=61, d_model=7168, n_heads=128, n_kv=128,
    d_ff=18432, vocab=129280, seq=4096, batch=15360, phase="train",
    moe_experts=256, moe_topk=8, gpu_budget=2048, n_dense_layers=3,
    d_ff_expert=2048, moe_shared=1, q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    mtp_layers=1)

KIND_TABLE_WORKLOADS: Tuple[LLMWorkload, ...] = (DEEPSEEK_V3,)


def inference_workload(base: LLMWorkload, phase: str, batch: int = 32,
                       seq: int = 2048) -> LLMWorkload:
    return dataclasses.replace(base, phase=phase, batch=batch, seq=seq)


def from_model_config(cfg: ModelConfig, shape: ShapeConfig) -> LLMWorkload:
    """Bridge: assigned runtime architectures as DSE benchmarks."""
    heads = max(cfg.n_heads, 1)
    d_ff = cfg.d_ff
    if cfg.family in ("ssm", "hybrid") and d_ff == 0:
        d_ff = 2 * cfg.d_model      # SSD GEMM-equivalent inner width
    return LLMWorkload(
        name=cfg.name,
        n_layers=cfg.num_layers,
        d_model=cfg.d_model,
        n_heads=heads,
        n_kv=max(cfg.n_kv, 1),
        d_ff=d_ff,
        vocab=cfg.vocab,
        seq=shape.seq_len,
        batch=shape.global_batch,
        phase=shape.kind,
        moe_experts=cfg.moe.num_experts if cfg.moe else 0,
        moe_topk=cfg.moe.top_k if cfg.moe else 0,
        gpu_budget=max(1, cfg.param_count() * 8 // (80 * 2 ** 30)),
    )
