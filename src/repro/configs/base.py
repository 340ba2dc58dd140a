"""Model/runtime configuration.

One `ModelConfig` describes any architecture in the assigned pool; the
per-arch modules in this package instantiate it with the exact public
dims. `reduced()` derives the CPU smoke-test config (same family, tiny
dims) required by the spec.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    group_size: int = 256           # tokens per routing group (GShard-style)
    dense_ff: int = 0               # Arctic: parallel dense-residual FFN width
    router_z_coef: float = 1e-3
    load_balance_coef: float = 1e-2
    # "einsum": GShard one-hot dispatch — O(tokens*E*C) bytes but cleanly
    #   SPMD-partitionable (default; E*C per token = top_k*S*cf, so the
    #   group size S controls the memory).
    # "gather": index-based dispatch — O(tokens*topk) bytes, but XLA's
    #   partitioner cannot batch-partition the scatter at jit level and
    #   replicates instead (measured: 28 GiB all-gathers per layer on
    #   arctic-480b; see EXPERIMENTS §Perf). Used on single-host paths.
    dispatch: str = "einsum"


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    n_groups: int = 1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0               # 0 -> d_model // n_heads
    # attention flavour
    window: Optional[int] = None    # sliding-window size (Mixtral)
    qk_norm: bool = False           # Qwen3
    qkv_bias: bool = False          # Qwen1.5 / Qwen2-VL
    rope_theta: float = 10_000.0
    use_rope: bool = True           # Whisper uses absolute embeddings
    mrope_sections: Optional[Tuple[int, int, int]] = None  # Qwen2-VL M-RoPE
    tie_embeddings: bool = False
    # MoE / SSM / hybrid extras
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    attn_every: int = 0             # hybrid: shared attn block period (Zamba2)
    shared_attn_lora_rank: int = 0  # Zamba2 per-invocation LoRA on shared block
    # hybrid: the mixer of each layer ("mamba" | "attention"), each layer
    # with its own weights and a SwiGLU MLP after its mixer (Granite 4.0-H);
    # a JSON list is accepted and kept as a tuple. Empty = not interleaved.
    layer_types: Tuple[str, ...] = ()
    # Granite-style multipliers; the defaults leave the computation as it
    # is for every other model
    norm_eps: float = 1e-6                  # RMSNorm epsilon (ln keeps 1e-5)
    embedding_multiplier: float = 1.0       # embeddings scaled on lookup
    # softmax scale; None = head_dim^-0.5
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0        # each branch scaled before its add
    logits_scaling: float = 1.0             # logits divided by this
    # encoder-decoder (Whisper)
    n_enc_layers: int = 0
    enc_ctx: int = 0                # encoder frames (stub frontend output)
    # block flavour
    norm: str = "rms"               # rms | ln (Whisper)
    mlp: str = "swiglu"             # swiglu | gelu (Whisper)
    # sharding behaviour
    # When kv/q heads don't divide the model axis (qwen1.5: 40 heads on
    # a 16-wide axis), shard the q-sequence dim instead of replicating
    # attention activations (context parallelism). Off in the
    # paper-faithful baseline; §Perf iteration 1.
    shard_attn_seq: bool = False
    # "free": leave non-divisible attention dims UNCONSTRAINED (XLA may
    # factor 40 heads as 8x2); "replicate": force replication (the
    # original baseline semantics, kept for §Perf before/after).
    constrain_mode: str = "free"
    # f32 attention I/O (baseline) vs bf16 I/O with f32 accumulation
    # (the Pallas flash kernel's numerics; halves attention-side HBM and
    # the dx all-reduce bytes). §Perf lever.
    attn_f32_io: bool = True
    # numerics / compilation
    vocab_pad_to: int = 256         # Megatron-style vocab padding (shardability)
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "full"             # none | full | dots
    scan_layers: bool = True
    max_position: int = 1 << 20
    # activation attention chunking (XLA online-softmax path)
    attn_chunk: int = 2048

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            if len(self.layer_types) != self.n_layers:
                raise ValueError(f"{len(self.layer_types)} layer_types for "
                                 f"{self.n_layers} layers")
            bad = set(self.layer_types) - {"mamba", "attention"}
            if bad:
                raise ValueError(f"unknown layer types {sorted(bad)}")

    @property
    def layer_period(self) -> int:
        """Shortest period of layer_types that divides the depth: the
        interleaved stack scans over whole periods."""
        lt = self.layer_types
        return next(p for p in range(1, len(lt) + 1)
                    if len(lt) % p == 0 and lt == lt[:p] * (len(lt) // p))

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_to
        return ((self.vocab + m - 1) // m) * m

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """Can this arch serve 500k-token contexts? (DESIGN §6)."""
        return self.family in ("ssm", "hybrid") or self.window is not None

    def reduced(self) -> "ModelConfig":
        """Same family, toy dims — the per-arch CPU smoke config."""
        kw = dict(
            name=self.name + "-reduced",
            n_layers=min(self.n_layers, 2),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // max(self.n_heads, 1))),
            head_dim=16,
            d_ff=128,
            vocab=256,
            max_position=4096,
            attn_chunk=64,
        )
        if self.mrope_sections is not None:
            kw["mrope_sections"] = (2, 3, 3)   # head_dim 16 -> 8 freq slots
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                group_size=32,
                dense_ff=64 if self.moe.dense_ff else 0,
            )
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=8, chunk=16)
        if self.n_enc_layers:
            kw["n_enc_layers"] = 2
            kw["enc_ctx"] = 32
        if self.attn_every:
            kw["attn_every"] = 2
        if self.layer_types:
            # one layer of each kind, in order of first appearance, twice
            kinds = tuple(dict.fromkeys(self.layer_types))
            kw["layer_types"] = kinds * 2
            kw["n_layers"] = 2 * len(kinds)
        if self.window is not None:
            kw["window"] = 32
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) column: what to lower and how big."""
    name: str                       # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                       # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = (
    ShapeCell("train_4k", "train", 4_096, 256),
    ShapeCell("prefill_32k", "prefill", 32_768, 32),
    ShapeCell("decode_32k", "decode", 32_768, 128),
    ShapeCell("long_500k", "decode", 524_288, 1),
)


def get_shape(name: str) -> ShapeCell:
    for s in SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)
