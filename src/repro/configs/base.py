"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a frozen ``ModelConfig``;
input shapes are ``ShapeConfig`` entries from the public shape table;
``RunConfig`` binds (model, shape, mesh, train/serve knobs) for the
launchers.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture. Field names follow the assignment table."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    vocab_size: int
    # --- attention ---
    num_heads: int = 0               # 0 => attention-free
    num_kv_heads: int = 0
    head_dim: int = 0                # 0 => d_model // num_heads
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0          # 0 => full attention
    global_attn_layers: Tuple[int, ...] = ()   # layers forced to full attn (hybrid)
    n_meta_tokens: int = 0           # learned always-visible prefix (hymba)
    # --- latent attention (MLA, DeepSeek-V3); kv_lora_rank 0 => not MLA ---
    kv_lora_rank: int = 0            # width of the cached latent c_kv
    q_lora_rank: int = 0             # 0 => a plain q projection
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0        # roped key width, shared by all heads
    v_head_dim: int = 0
    # --- mlp / moe ---
    d_ff: int = 0                    # dense FFN hidden (0 for pure-ssm)
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                # per-expert hidden dim
    capacity_factor: float = 1.25
    # softmax | sigmoid (DeepSeek-V3's noaux_tc: a score-correction bias
    # in the choice only); the chosen experts' gates are renormalised
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0        # one SwiGLU MLP of n x moe_d_ff
    first_k_dense: int = 0           # leading layers with a dense d_ff MLP
    # --- ssm (mamba2 SSD) ---
    ssm_state: int = 0               # N
    ssm_heads: int = 0
    ssm_head_dim: int = 0            # P
    ssm_expand: int = 2
    conv_width: int = 4
    ssd_chunk: int = 128
    # --- encoder/decoder ---
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq: int = 0             # e.g. whisper: 1500 frames after conv stub
    # --- modality frontend (STUB per prompt) ---
    frontend: str = "none"           # none | audio_stub | vq_stub
    # --- numerics ---
    dtype: str = "bfloat16"          # activation / compute dtype
    param_dtype: str = "float32"     # stored parameter dtype
    # --- misc ---
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    source: str = ""                 # provenance string from the assignment table

    # ---- derived helpers ----
    def padded_vocab(self, multiple: int = 128) -> int:
        """Megatron-style vocab padding: embedding/unembedding tables are
        padded to a 128 multiple so the vocab dim TP-shards evenly (the
        assigned archs include 50280/32001/51866-sized vocabs, none of
        which divide a 16-way mesh axis). Labels never reference pad ids;
        the padded classes train as ordinary never-observed classes."""
        return -(-self.vocab_size // multiple) * multiple

    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    def qk_head_dim(self) -> int:
        """Width of a query or key head: latent attention's nope + rope
        parts, else ``hd()``."""
        if self.is_mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.hd()

    def latent_width(self) -> int:
        """Values an MLA layer caches per position: c_kv and k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    def validate(self) -> None:
        assert self.family in FAMILIES, self.family
        if self.family != "ssm":
            assert self.num_heads > 0
            assert self.num_kv_heads > 0
            assert self.num_heads % self.num_kv_heads == 0
        if self.is_moe:
            assert self.experts_per_token > 0 and self.moe_d_ff > 0
            assert self.router_scoring in ("softmax", "sigmoid")
            assert 0 <= self.first_k_dense < self.num_layers
            assert self.d_ff > 0 or not self.first_k_dense
        if self.is_mla:
            assert self.qk_nope_head_dim > 0 and self.v_head_dim > 0
            assert self.qk_rope_head_dim % 2 == 0
        if self.family in ("ssm", "hybrid"):
            assert self.ssm_state > 0
        if self.is_encoder_decoder:
            assert self.num_encoder_layers > 0 and self.encoder_seq > 0

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-flops)."""
        return _param_count(self)

    def active_param_count(self) -> int:
        return _param_count(self, active_only=True)

    def reduced(self, **overrides: Any) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small = dict(
            num_layers=2,
            d_model=64,
            vocab_size=256,
            head_dim=16,
        )
        if self.num_heads:
            small.update(num_heads=4, num_kv_heads=max(1, min(self.num_kv_heads, 2)))
        if self.d_ff:
            small.update(d_ff=128)
        if self.is_moe:
            small.update(num_experts=4, experts_per_token=2, moe_d_ff=64,
                         first_k_dense=min(self.first_k_dense, 1))
            if self.first_k_dense:
                small.update(num_layers=3)
        if self.is_mla:
            small.update(kv_lora_rank=32, qk_nope_head_dim=16,
                         qk_rope_head_dim=8, v_head_dim=16, head_dim=0,
                         q_lora_rank=24 if self.q_lora_rank else 0)
        if self.ssm_state:
            di = small["d_model"] * self.ssm_expand
            small.update(ssm_state=16, ssm_heads=di // 16, ssm_head_dim=16,
                         ssd_chunk=16)
        if self.is_encoder_decoder:
            small.update(num_encoder_layers=2, encoder_seq=32)
        if self.sliding_window:
            small.update(sliding_window=16)
        if self.global_attn_layers:
            small.update(global_attn_layers=(0,))
        if self.n_meta_tokens:
            small.update(n_meta_tokens=4)
        small.update(dtype="float32", param_dtype="float32")
        small.update(overrides)
        return dataclasses.replace(self, name=self.name + "-smoke", **small)


def _param_count(c: ModelConfig, active_only: bool = False) -> int:
    d = c.d_model
    hd = c.qk_head_dim()
    n = 0
    # embeddings (+ untied unembed)
    n += c.vocab_size * d
    if not c.tie_embeddings:
        n += c.vocab_size * d

    def attn_params() -> int:
        if c.is_mla:
            H, r = c.num_heads, c.kv_lora_rank
            q = (d * H * hd if not c.q_lora_rank else
                 d * c.q_lora_rank + c.q_lora_rank + c.q_lora_rank * H * hd)
            kv = d * c.latent_width() + r + r * H * (c.qk_nope_head_dim
                                                     + c.v_head_dim)
            return q + kv + H * c.v_head_dim * d
        q = d * c.num_heads * hd
        kv = 2 * d * c.num_kv_heads * hd
        o = c.num_heads * hd * d
        qknorm = 2 * hd if c.qk_norm else 0
        return q + kv + o + qknorm

    def dense_ffn(width: int) -> int:
        return 3 * d * width  # SwiGLU: gate, up, down

    def moe_ffn() -> int:
        e = c.experts_per_token if active_only else c.num_experts
        shared = dense_ffn(c.n_shared_experts * c.moe_d_ff)
        bias = c.num_experts if c.router_scoring == "sigmoid" else 0
        # experts + router
        return e * 3 * d * c.moe_d_ff + d * c.num_experts + bias + shared

    def ssm_params() -> int:
        di = c.d_inner()
        heads = c.ssm_heads or max(1, di // max(1, c.ssm_head_dim or 64))
        # in_proj produces [z, x, B, C, dt] (mamba2): 2*di + 2*N*groups + heads
        in_proj = d * (2 * di + 2 * c.ssm_state + heads)
        conv = c.conv_width * (di + 2 * c.ssm_state)
        out = di * d
        extra = di + 2 * heads  # norm gate + A, D
        return in_proj + conv + out + extra

    per_layer_norms = 2 * d
    for layer in range(c.num_layers):
        n += per_layer_norms
        if c.family == "ssm":
            n += ssm_params()
            continue
        if c.family == "hybrid":
            n += attn_params() + ssm_params() + dense_ffn(c.d_ff)
            continue
        n += attn_params()
        n += (moe_ffn() if c.is_moe and layer >= c.first_k_dense
              else dense_ffn(c.d_ff))
    if c.is_encoder_decoder:
        for _ in range(c.num_encoder_layers):
            # encoder self-attn + ffn; decoder layers above additionally carry
            # cross-attention
            n += per_layer_norms + attn_params() + dense_ffn(c.d_ff)
        n += c.num_layers * (attn_params() + d)  # cross-attn + its norm
        n += c.encoder_seq * d                   # learned encoder positions
    n += d  # final norm
    return n


# ---------------------------------------------------------------------------
# Shape table (assigned; identical for every LM arch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Mesh / run configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"          # adamw | adafactor
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    num_microbatches: int = 1
    grad_accum_dtype: str = "float32"  # float32 | bfloat16
    remat_policy: str = "full"         # none | full | dots
    grad_compression: str = "none"     # none | int8_ef | topk_ef
    seed: int = 0
    zero1: bool = True                 # shard optimizer state over data axis


@dataclass(frozen=True)
class ServeConfig:
    kv_dtype: str = "bfloat16"         # bfloat16 | int8
    kv_seq_shard: bool = False         # shard KV seq over data axis (long ctx)
    max_decode_steps: int = 32
    temperature: float = 0.0


@dataclass(frozen=True)
class ShardingConfig:
    """Which logical axes map to which mesh axes (the perf levers)."""
    fsdp_axis: str = "data"            # params' non-TP dim
    tp_axis: str = "model"
    batch_axes: Tuple[str, ...] = ("pod", "data")
    seq_shard_activations: bool = False  # SP: shard saved residuals' seq over model
    fsdp_params: bool = True           # FSDP-shard params over data axis


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    mesh: MeshConfig = SINGLE_POD
    train: TrainConfig = TrainConfig()
    serve: ServeConfig = ServeConfig()
    sharding: ShardingConfig = ShardingConfig()

    def replace(self, **kw: Any) -> "RunConfig":
        return dataclasses.replace(self, **kw)
