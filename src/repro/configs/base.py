"""Model/run configuration dataclasses + arch registry.

Every assigned architecture provides ``full()`` (exact published config) and
``smoke()`` (reduced same-family config for CPU tests) via
``repro.configs.registry``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp


@dataclass(frozen=True)
class ConSmaxConfig:
    """Learnable-normalizer config (the paper's contribution)."""
    beta_init_lo: float = 0.5        # paper: beta ~ U[0.5, 2.5]
    beta_init_hi: float = 2.5
    gamma_init: float = 100.0        # paper: gamma = 100
    per_head: bool = True
    learnable: bool = True
    # inference-time merged constant C = e^{-beta}/gamma (paper Eq.3, sign fixed)


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    d_ff_expert: int = 0             # expert hidden size
    capacity_factor: float = 1.25    # 0 = dropless: every token reaches
                                     # all of its top-k experts (grouped
                                     # matmuls over the sorted rows)
    layer_period: int = 1            # MoE every k-th layer (jamba: 2)
    aux_loss_weight: float = 0.01
    router_norm: str = "softmax"     # "softmax" | "consmax" (extension)

    @property
    def dropless(self) -> bool:
        """Every token reaches all of its top-k experts (``capacity_factor``
        0); the one test of that setting, for every reader."""
        return self.capacity_factor == 0


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (transformers' ``_compute_yarn_parameters``,
    ``truncate`` true): frequencies below the correction range are
    interpolated by ``factor``, those above it kept, a linear ramp between;
    cos and sin are scaled by ``attention_factor``."""
    factor: float = 16.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                 # 0 -> ceil(d_model/16)
    chunk: int = 256                 # chunkwise scan length (memory control)


@dataclass(frozen=True)
class XLSTMConfig:
    proj_factor: float = 2.0
    d_conv: int = 4
    slstm_every: int = 8             # xLSTM[7:1]: 1 sLSTM per 8 blocks
    chunk: int = 256
    stabilizer: str = "max"          # "max" (faithful) | "consmax" (extension)


@dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                      # dense|moe|vlm|ssm|audio|hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # --- attention flavour ---
    score_norm: str = "consmax"      # "softmax" | "consmax" | "softermax"
    consmax: ConSmaxConfig = field(default_factory=ConSmaxConfig)
    qkv_bias: bool = False
    rope_style: str = "half"         # "half" | "interleaved" (glm 2d) | "none"
    rope_fraction: float = 1.0       # chatglm: 0.5
    rope_theta: float = 10000.0
    rope_yarn: Optional[YarnConfig] = None  # YaRN on full-attention layers
                                     # only; "local" kinds keep plain RoPE
    attn_softcap: float = 0.0        # gemma2: 50.0 ; grok: 30.0 ; 0 = off
    final_softcap: float = 0.0       # gemma2: 30.0
    window: int = 0                  # sliding-window size for "local" layers
    block_pattern: tuple = ("attn",) # repeating layer pattern, e.g.
                                     # ("local","global") or 7*("mamba",)+("attn",)
    cross_attn: bool = False         # musicgen: cross-attend to conditioning
    n_cond_tokens: int = 0
    sinusoidal_pos: bool = False     # musicgen/gpt2: additive abs positions
    # --- mlp flavour ---
    mlp: str = "silu_glu"            # "silu_glu" | "gelu_glu" | "gelu"
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    post_block_norm: bool = False    # gemma2 sandwich norms
    embed_scale: bool = False        # gemma2: scale embeddings by sqrt(d)
    tie_embeddings: bool = True
    # --- frontends (stubs per assignment) ---
    frontend: str = "tokens"         # "tokens" | "patches" (vlm) | "frames" (audio)
    # --- mixture / ssm ---
    moe: Optional[MoEConfig] = None
    mamba: Optional[MambaConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    # --- dtypes ---
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def pattern_period(self) -> int:
        return len(self.block_pattern)

    @property
    def n_super_layers(self) -> int:
        assert self.n_layers % self.pattern_period == 0, (
            self.arch_id, self.n_layers, self.block_pattern)
        return self.n_layers // self.pattern_period

    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 256
    seq_len: int = 4096
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    remat: str = "full"              # "none" | "full" | "dots"
    microbatch: int = 0              # 0 = no gradient accumulation
    fsdp: bool = True                # shard params/opt over data axis
    grad_compression: str = "none"   # "none" | "int8_ef" (error feedback)
    q_chunk: int = 2048              # blockwise-attention tile sizes
    kv_chunk: int = 1024
    seed: int = 0


@dataclass(frozen=True)
class ServeConfig:
    batch: int = 128
    max_seq: int = 32_768
    prefill_chunk: int = 0           # append-at-index prefill chunk size:
                                     # ONE compiled prefill shape (1, chunk);
                                     # 0 resolves to min(2048, max_seq)
    kv_cache_dtype: str = "bfloat16"
    seq_shard_kv: bool = False       # shard KV cache along sequence (500k cells)
    q_chunk: int = 2048              # prefill blockwise-attention tiles
    kv_chunk: int = 1024
    # --- continuous batching (serve/scheduler.py + engine.py) ---
    max_slots: int = 8               # concurrent requests in the decode batch
    fused_sampling: bool = True      # sample logits->token INSIDE the jitted
                                     # prefill/decode steps (per-slot
                                     # serve/sampling.SamplingParams banks;
                                     # steps return (b,) int32 tokens, no
                                     # per-token (b, vocab) host transfer).
                                     # False = legacy logits-returning steps
                                     # with host-side sampling (dryrun cells
                                     # and the benchmark A/B baseline)
    prefill_budget: int = 0          # max prefill tokens per engine iteration
                                     # (0 = one prefill_chunk per iteration)
    decode_kernel: bool = False      # split-KV consmax_decode Pallas kernel
    decode_kv_block: int = 256       # KV shard size for the split-KV kernel
    prefill_kernel: bool = False     # fused consmax_prefill Pallas kernel
                                     # for append-at-index prefill chunks
                                     # (contiguous and paged)
    prefill_kv_block: int = 512      # KV shard size for the prefill kernel
                                     # grid (contiguous caches)
    fill_bound: bool = True          # bound the serving kernels' KV grids
                                     # by the traced per-slot fill instead
                                     # of cache capacity (fill stays a
                                     # value — no extra compiled shape);
                                     # False = capacity-swept A/B baseline
    score_norm: Optional[str] = None # the served model's score_norm, when
                                     # known at config time: lets the kernel
                                     # flags fail at CONSTRUCTION on a
                                     # softmax/softermax arch (make_serve_fns
                                     # re-checks against the real ModelConfig
                                     # either way)
    # --- paged KV (shared page pool across slots) ---
    paged_kv: bool = False           # slots map logical rows onto pool pages
    page_size: int = 256             # KV rows per page (must divide
                                     # prefill_chunk so chunk writes stay
                                     # page-regular)
    num_pages: int = 0               # pool capacity; 0 resolves to
                                     # max_slots * ceil(max_seq / page_size)
                                     # (no oversubscription — set lower to
                                     # share pages across short requests)
    prefix_cache: bool = True        # prefix-sharing page cache: identical
                                     # prompt prefixes map to the same
                                     # physical pages (refcounted, COW);
                                     # warm requests skip the shared rows'
                                     # prefill entirely
    prefix_evict: str = "lru"        # reclaim order for refcount-0 cached
                                     # pages when the free list runs dry:
                                     # "lru" (release order) | "fifo"
                                     # (registration order)
    # --- device mesh (distributed/serve_mesh.py) ---
    tp: int = 1                      # tensor-parallel shards along the
                                     # "model" mesh axis: KV heads (and the
                                     # q/o head projections + per-head
                                     # ConSmax beta/gamma) split across
                                     # devices; per-shard partials combine
                                     # by ONE output-sized fp32 psum (no
                                     # log-sum-exp rescale — ConSmax has no
                                     # denominator)
    seq_shards: int = 1              # page-pool shards along the "seq" mesh
                                     # axis: physical pages spread across
                                     # devices (shard d owns the contiguous
                                     # block [d*P/ns, (d+1)*P/ns)), slot page
                                     # position j always backed by shard
                                     # j // ceil(maxpps/ns) — a request
                                     # within one block stays whole-shard
                                     # (token bit-identity: foreign shards
                                     # contribute exact +0.0 partials), a
                                     # longer one spills block by block so
                                     # long_500k spreads its pages. Requires
                                     # paged_kv + fill_bound (each shard's
                                     # kernels skip non-local pages via the
                                     # -1 holes in its localized table,
                                     # which only fill-bounded grids gate
                                     # on)

    def __post_init__(self):
        # invalid shapes fail HERE, not deep inside _append_cache_write /
        # the page-table scatter once a request is already being served
        if self.prefill_chunk == 0:
            object.__setattr__(self, "prefill_chunk",
                               min(2048, self.max_seq))
        if self.prefill_chunk < 0 or self.max_seq <= 0:
            raise ValueError(
                f"ServeConfig: prefill_chunk ({self.prefill_chunk}) and "
                f"max_seq ({self.max_seq}) must be positive")
        if self.prefill_chunk > self.max_seq:
            raise ValueError(
                f"ServeConfig: prefill_chunk ({self.prefill_chunk}) exceeds "
                f"max_seq ({self.max_seq}) — an append chunk could not fit "
                "a slot's KV rows")
        if self.kv_cache_dtype not in ("bfloat16", "bf16", "int8",
                                       "fp8_e4m3"):
            raise ValueError(
                f"ServeConfig: kv_cache_dtype must be one of 'bfloat16', "
                f"'bf16', 'int8', 'fp8_e4m3', got {self.kv_cache_dtype!r}")
        if self.prefill_kv_block <= 0 or self.decode_kv_block <= 0:
            raise ValueError(
                f"ServeConfig: prefill_kv_block ({self.prefill_kv_block}) "
                f"and decode_kv_block ({self.decode_kv_block}) must be "
                "positive")
        if self.score_norm is not None and self.score_norm != "consmax":
            flags = [name for name, on in (("decode_kernel",
                                            self.decode_kernel),
                                           ("prefill_kernel",
                                            self.prefill_kernel)) if on]
            if flags:
                verb = "require" if len(flags) > 1 else "requires"
                raise ValueError(
                    f"ServeConfig: {' and '.join(flags)} {verb} "
                    f"score_norm='consmax' (got {self.score_norm!r}): the "
                    "fused serving kernels have no softmax/softermax path")
        if self.paged_kv:
            if self.page_size <= 0:
                raise ValueError(
                    f"ServeConfig: page_size ({self.page_size}) must be "
                    "positive")
            if self.prefill_chunk % self.page_size:
                raise ValueError(
                    f"ServeConfig: page_size ({self.page_size}) must divide "
                    f"prefill_chunk ({self.prefill_chunk}) so prefill chunk "
                    "writes start page-aligned")
            if self.num_pages == 0:
                object.__setattr__(
                    self, "num_pages",
                    self.max_slots * self.max_pages_per_slot)
            if self.num_pages < self.max_pages_per_slot:
                raise ValueError(
                    f"ServeConfig: num_pages ({self.num_pages}) below "
                    f"max_pages_per_slot ({self.max_pages_per_slot}) — even "
                    "a single max_seq request could not be served")
            if self.prefix_evict not in ("lru", "fifo"):
                raise ValueError(
                    f"ServeConfig: prefix_evict must be 'lru' or 'fifo', "
                    f"got {self.prefix_evict!r}")
        if self.tp < 1 or self.seq_shards < 1:
            raise ValueError(
                f"ServeConfig: tp ({self.tp}) and seq_shards "
                f"({self.seq_shards}) must be >= 1")
        if self.seq_shards > 1:
            if not self.paged_kv:
                raise ValueError(
                    f"ServeConfig: seq_shards ({self.seq_shards}) > 1 "
                    "requires paged_kv — only the page pool has a device "
                    "dimension to shard (contiguous caches replicate)")
            if not self.fill_bound:
                raise ValueError(
                    f"ServeConfig: seq_shards ({self.seq_shards}) > 1 "
                    "requires fill_bound — a shard's localized page table "
                    "holds -1 for non-local pages, and only the "
                    "fill-bounded kernel grids gate their compute on "
                    "table entries >= 0 (the capacity-swept paths clamp "
                    "-1 to page 0 and would read another slot's data)")
            if self.num_pages % self.seq_shards:
                raise ValueError(
                    f"ServeConfig: seq_shards ({self.seq_shards}) must "
                    f"divide num_pages ({self.num_pages}) — pages shard "
                    "into equal contiguous per-device blocks")

    @property
    def max_pages_per_slot(self) -> int:
        return -(-self.max_seq // self.page_size)

    @property
    def mesh_shape(self) -> tuple:
        """(tp, seq_shards) — the ("model", "seq") device mesh the sharded
        serving steps run on; (1, 1) means single-device (no shard_map)."""
        return (self.tp, self.seq_shards)


SHAPES = {
    # name: (seq_len, global_batch, kind)
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}
