"""Shared model config + primitive layers (pure-functional JAX).

Every architecture in the assigned pool is expressible through ``ModelConfig``
feature flags; the assembly lives in transformer.py / encdec.py. Params are
nested dicts of jnp arrays; layer stacks keep a leading layer axis and are
consumed by ``jax.lax.scan`` so 64-layer models lower to compact HLO.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    arch_type: str = "dense"  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""          # citation (paper / model card)

    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0          # 0 → d_model // n_heads
    d_ff: int = 512
    vocab_size: int = 1024

    # attention features
    attention: str = "gqa"     # gqa | mla | none
    lora_rank: int = 0         # >0 → LoRA adapter on the q projection
                               # (adapter-only federation ships just these)
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int = 0    # 0 → full causal; >0 → local attention window
    attn_logit_softcap: float = 0.0
    attn_qblock: int = 256     # chunked-attention q-tile (§Perf knob: bigger tile
                               # → fewer K/V HBM re-reads, more VMEM per tile)
    attn_probs_bf16: bool = False  # cast softmax probs to bf16 before P·V
                                   # (§Perf H2: halves prob traffic; ~1e-3 rel err)

    # activation
    activation: str = "swiglu"  # swiglu | geglu | gelu

    # MoE (models/moe.py): a router over num_experts, of which the layer holds
    # experts [first_expert, first_expert + experts_held) (0 → all of them)
    num_experts: int = 0
    experts_per_token: int = 0
    experts_held: int = 0
    first_expert: int = 0
    moe_d_ff: int = 0          # routed and shared experts' width (0 → d_ff)
    num_shared_experts: int = 0
    router_score: str = "softmax"  # softmax | sigmoid (DeepSeek-V3: selection bias,
                                   # sequence-wise balance loss)
    routed_scaling: float = 1.0    # factor on the normalised top-k weights
    router_aux_weight: float = 0.01
    first_dense_layers: int = 0    # leading dense layers before the MoE stack

    # MLA (MiniCPM3 / DeepSeek-style)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    mla_absorb: bool = False  # absorbed-matmul decode (§Perf hillclimb)

    # SSM (Mamba2 SSD)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256

    # hybrid layer pattern, e.g. ("rglru", "rglru", "attn") for RecurrentGemma
    layer_pattern: tuple[str, ...] = ()
    rglru_c: float = 8.0
    conv1d_width: int = 4

    # encoder-decoder (audio)
    encoder_layers: int = 0

    # modality frontend stub: "vision" feeds patch embeddings, "audio" frames
    frontend: str = ""
    frontend_tokens: int = 0   # patches / frames per example

    remat_policy: str = "full"  # full | dots (save matmul outputs — §Perf H3:
                                # cuts remat recompute FLOPs for more memory)
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    embed_scale: float = 0.0   # embedding multiplier (0 → sqrt(d_model))
    vocab_pad_to: int = 256
    dtype: str = "bfloat16"
    # long-context decode support: dense archs flip this on for long_500k
    long_context_window: int = 8192

    # ---- derived ----
    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return ((self.vocab_size + p - 1) // p) * p

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def pattern(self) -> tuple[str, ...]:
        if self.layer_pattern:
            return self.layer_pattern
        if self.arch_type == "ssm":
            return ("ssm",)
        return ("moe_attn",) if self.num_experts else ("attn",)

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the routed experts this layer holds."""
        return self.first_expert, self.experts_held or self.num_experts

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: ≤1 leading dense layer and ≤2 pattern units of
        layers, d_model ≤ 256, ≤4 experts (all held)."""
        unit = len(self.pattern)
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        first_dense = min(self.first_dense_layers, 1)
        return self.replace(
            name=self.name + "-reduced",
            n_layers=min(self.n_layers, first_dense + 2 * unit),
            first_dense_layers=first_dense,
            encoder_layers=min(self.encoder_layers, 2) if self.encoder_layers else 0,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=min(self.n_kv_heads, n_heads),
            head_dim=64 if self.head_dim else 0,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            num_experts=min(self.num_experts, 4) if self.num_experts else 0,
            experts_per_token=min(self.experts_per_token, 2) if self.experts_per_token else 0,
            experts_held=0,
            first_expert=0,
            moe_d_ff=min(self.moe_d_ff, 128) if self.moe_d_ff else 0,
            q_lora_rank=min(self.q_lora_rank, 64) if self.q_lora_rank else 0,
            kv_lora_rank=min(self.kv_lora_rank, 32) if self.kv_lora_rank else 0,
            ssm_state=min(self.ssm_state, 32) if self.ssm_state else 0,
            ssm_chunk=32 if self.ssm_state else self.ssm_chunk,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            frontend_tokens=min(self.frontend_tokens, 16) if self.frontend_tokens else 0,
            dtype="float32",
        )


# --------------------------------------------------------------------------
# Primitive layers (functional: init_* returns params, apply is a function)
# --------------------------------------------------------------------------


def dense_init(rng, d_in: int, d_out: int, dtype, scale: float | None = None) -> dict:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale
    return {"w": w.astype(dtype)}


def dense_apply(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["w"]


def rmsnorm_init(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def embedding_init(rng, vocab: int, d: int, dtype) -> dict:
    return {"table": (jax.random.normal(rng, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embedding_apply(p: dict, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["table"], tokens, axis=0)


def unembed_apply(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Tied unembedding: logits in f32 (loss stability)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32), p["table"].astype(jnp.float32))


# -- rotary ------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    freqs = rope_frequencies(x.shape[-1], theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- gated MLP ----------------------------------------------------------------


def mlp_init(rng, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    dt = cfg.jdtype
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "wi": dense_init(k1, cfg.d_model, d_ff, dt),
            "wg": dense_init(k2, cfg.d_model, d_ff, dt),
            "wo": dense_init(k3, d_ff, cfg.d_model, dt),
        }
    return {
        "wi": dense_init(k1, cfg.d_model, d_ff, dt),
        "wo": dense_init(k3, d_ff, cfg.d_model, dt),
    }


def mlp_apply(p: dict, x: jnp.ndarray, activation: str) -> jnp.ndarray:
    h = dense_apply(p["wi"], x)
    if activation == "swiglu":
        h = jax.nn.silu(h) * dense_apply(p["wg"], x)
    elif activation == "geglu":
        h = jax.nn.gelu(h, approximate=True) * dense_apply(p["wg"], x)
    elif activation == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    else:
        raise ValueError(f"unknown activation {activation}")
    return dense_apply(p["wo"], h)


# -- losses -------------------------------------------------------------------


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, valid_vocab: int | None = None) -> jnp.ndarray:
    """Per-token CE in f32; padded vocab tail masked out."""
    logits = logits.astype(jnp.float32)
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) < valid_vocab
        logits = jnp.where(mask, logits, -1e30)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold


def token_accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
