"""Model configuration — one dataclass drives the whole zoo.

Every assigned architecture is a :class:`ModelConfig` instance in
``repro.configs.<id>``; the generic LM in ``repro.models.lm`` assembles the
right blocks from these fields.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # block family: 'dense' | 'moe' | 'rwkv6' | 'hymba'
    block: str = "dense"
    head_dim: Optional[int] = None          # default d_model // n_heads

    # attention details
    qkv_bias: bool = False
    sliding_window: Optional[int] = None    # tokens; None = full causal
    rope_theta: float = 10_000.0
    # TP alignment (§Perf): extra query heads per KV group, output-masked to
    # zero so the model is mathematically unchanged. Lets the padded head
    # count divide the model axis (e.g. qwen2 28->32 for TP=16), which turns
    # per-chunk attention-logits all-reduces into plain head sharding.
    q_head_pad: int = 0

    @property
    def n_rep(self) -> int:
        return self.n_heads // self.n_kv_heads + self.q_head_pad

    @property
    def n_heads_padded(self) -> int:
        return self.n_kv_heads * self.n_rep

    # MoE
    moe_experts: int = 0
    moe_topk: int = 0
    moe_capacity: float = 1.25
    moe_group: int = 512                    # tokens per dispatch group

    # SSM (rwkv6 / hymba)
    ssm_state: int = 16                     # mamba state dim N (hymba)
    ssm_heads: int = 0                      # 0 = derive from d_model

    # encoder-decoder (whisper)
    enc_dec: bool = False
    enc_layers: int = 0
    enc_frames: int = 1500                  # stubbed conv frontend output length

    # multimodal prefix (internvl: precomputed patch embeddings)
    prefix_embed_len: int = 0

    # misc
    norm: str = "rms"                       # 'rms' | 'ln'
    act: str = "swiglu"                     # 'swiglu' | 'gelu'
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    param_dtype: str = "bfloat16"

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding shards on
        any (data x model) mesh factorization; losses/decode mask the pad."""
        return (self.vocab + 255) // 256 * 256

    @property
    def attn_free(self) -> bool:
        return self.block == "rwkv6"

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)
