"""Plain float32 reference of the DeepSeek-V3 decoder (Moonlight-16B-A3B):
forward pass and loss, for the tests of ``DecoderLM`` at a small size.

Written from the block's equations (arXiv:2412.19437 §2.1), not from the
program's layers: no sort, no grouping, no cache, no chunking. Every
contraction runs at ``Precision.HIGHEST``. It reads the sizes from a
``ModelConfig`` and the weights from the program's parameter tree.

Block: ``x += MLA(RMSNorm(x))``; ``x += FFN(RMSNorm(x))``, where FFN is a
SwiGLU MLP in the leading dense layers and the expert layer after them. The
blocks are read in order from ``head`` and from a single-unit ``blocks`` stack.

MLA: q = h·W_q (or h·W_qa → RMSNorm → ·W_qb) split into nope and rope parts;
c = RMSNorm(h·W_kva); k_rope = h·W_krope, shared by all heads; k_nope = c·W_kb,
v = c·W_vb; rotary on the rope parts (the two halves of each rope dimension
paired); causal softmax at 1/√(d_nope + d_rope).

Expert layer: s = sigmoid(h·W_r) (or softmax) over all experts; the top-k of
s + b (b = 0 for softmax) are chosen; the gate of a chosen expert is its s
over the chosen s's sum, times ``routed_scaling``, and 0 elsewhere. Each held
expert runs on every token and is multiplied by its gate; the shared experts
are added. Balance loss: DeepSeek-V3's sequence-wise loss (sigmoid) or the
Switch loss (softmax), weighted by ``router_aux_weight``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=HIGHEST)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, heads, d); position p rotates the pair (i, i + d/2) by p·θ^(−2i/d)."""
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mla(p: dict, cfg, h):
    B, S, _ = h.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if "wq" in p:
        q = _mm(h, p["wq"]["w"])
    else:
        q = _mm(_norm(_mm(h, p["wq_a"]["w"]), p["q_a_norm"]["scale"], cfg.norm_eps), p["wq_b"]["w"])
    q = q.reshape(B, S, H, dn + dr)
    c = _norm(_mm(h, p["wkv_a"]["w"]), p["kv_a_norm"]["scale"], cfg.norm_eps)
    k_rope = _rope(_mm(h, p["wk_rope"]["w"])[:, :, None, :], cfg.rope_theta)   # (B, S, 1, dr)
    k_nope = _mm(c, p["wk_b"]["w"]).reshape(B, S, H, dn)
    v = _mm(c, p["wv_b"]["w"]).reshape(B, S, H, dv)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cfg.rope_theta)
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope, precision=HIGHEST)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope[:, :, 0], precision=HIGHEST))
    scores = scores / math.sqrt(dn + dr)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v, precision=HIGHEST).reshape(B, S, H * dv)
    return _mm(out, p["wo"]["w"])


def ffn(p: dict, x, activation: str = "swiglu"):
    """SwiGLU, or tanh-GELU without a gate (grok-1's experts as configured)."""
    if activation == "swiglu":
        return _mm(jax.nn.silu(_mm(x, p["wi"]["w"])) * _mm(x, p["wg"]["w"]), p["wo"]["w"])
    if activation == "gelu":
        return _mm(jax.nn.gelu(_mm(x, p["wi"]["w"]), approximate=True), p["wo"]["w"])
    raise ValueError(f"the reference has no activation {activation!r}")


def gates(p: dict, cfg, h):
    """h: (N, D) → (scores (N, E), dense gates (N, E), chosen experts (N, k))."""
    logits = _mm(h, p["router"]["w"])
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores + p["router_bias"].astype(jnp.float32)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        select = scores
    _, idx = jax.lax.top_k(select, cfg.experts_per_token)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32).sum(1)    # (N, E)
    chosen = scores * onehot
    dense = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * cfg.routed_scaling
    return scores, dense, idx


def balance_loss(cfg, scores, idx, batch: int):
    E, k = cfg.num_experts, cfg.experts_per_token
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)                  # (N, E)
    if cfg.router_score == "sigmoid":
        s = scores.reshape(batch, -1, E)
        T = s.shape[1]
        f = chosen.reshape(batch, T, E).sum(1) * E / (k * T)
        P = jnp.mean(s / s.sum(-1, keepdims=True), axis=1)
        return jnp.mean(jnp.sum(f * P, axis=-1))
    first = jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32)
    return E * jnp.sum(jnp.mean(first, 0) * jnp.mean(scores, 0))


def experts(p: dict, cfg, h):
    """The held experts' part of the layer plus the shared experts, and the
    balance loss and chosen experts. h: (B, S, D)."""
    B, S, D = h.shape
    x = h.reshape(B * S, D)
    scores, dense, idx = gates(p, cfg, x)
    first, held = cfg.held_experts
    y = jnp.zeros_like(x)
    for e in range(held):
        w = {n: {"w": p[n]["w"][e]} for n in ("wi", "wg", "wo")}
        y = y + dense[:, first + e, None] * ffn(w, x, cfg.activation)
    if "shared" in p:
        y = y + ffn(p["shared"], x, cfg.activation)
    return y.reshape(B, S, D), balance_loss(cfg, scores, idx, B), idx


def forward(cfg, params: dict, tokens):
    """(B, S) tokens → (logits over the unpadded vocabulary (B, S, V), balance
    loss summed over the expert layers, chosen experts (expert layers, B·S, k))."""
    x = jnp.take(params["embed"]["table"].astype(jnp.float32), tokens, axis=0)
    x = x * (cfg.embed_scale or math.sqrt(cfg.d_model))
    layers = [params["head"][k] for k in sorted(params.get("head", {}))]
    for key, stacked in params["blocks"].items():
        n = jax.tree.leaves(stacked)[0].shape[0]
        layers += [jax.tree.map(lambda a, i=i: a[i], stacked) for i in range(n)]
    aux, routes = jnp.zeros((), jnp.float32), []
    for p in layers:
        x = x + mla(p["attn"], cfg, _norm(x, p["norm1"]["scale"], cfg.norm_eps))
        h = _norm(x, p["norm2"]["scale"], cfg.norm_eps)
        if "moe" in p:
            y, a, idx = experts(p["moe"], cfg, h)
            aux, routes = aux + a, routes + [idx]
        else:
            y = ffn(p["mlp"], h, cfg.activation)
        x = x + y
    x = _norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    table = params["unembed" if "unembed" in params else "embed"]["table"][: cfg.vocab_size]
    logits = jnp.einsum("bsd,vd->bsv", x, table.astype(jnp.float32), precision=HIGHEST)
    return logits, aux, jnp.stack(routes) if routes else None


def loss(cfg, params: dict, batch: dict):
    """Mean next-token cross-entropy plus the weighted balance loss."""
    logits, aux, _ = forward(cfg, params, batch["tokens"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold) + cfg.router_aux_weight * aux
