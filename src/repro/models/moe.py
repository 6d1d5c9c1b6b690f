"""Mixture-of-Experts layer: a router over every expert, a dropless grouped
matmul over the experts this layer holds.

Under expert parallelism each chip holds a share of a layer's experts. The
layer is told which (``ModelConfig.held_experts``: experts
[first, first + count) of the router's ``num_experts``, all of them unless
told otherwise). It routes every token over all experts, computes its held
experts' part of the output for the token-choices routed to them, and adds
the shared experts, which every share computes alike. What the other experts
add is left to the chips that hold them.

Routing (``ModelConfig.router_score``):

- ``softmax`` (grok-1, llama4): top-k of softmax(h·W_r), the chosen
  probabilities renormalised; the Switch load-balance loss.
- ``sigmoid`` (DeepSeek-V3, Moonlight): s = sigmoid(h·W_r); the experts are
  the top-k of s + b, where b (``router_bias``) is a correction bias that
  only selects; the weights are the chosen s renormalised, times
  ``routed_scaling``; DeepSeek-V3's complementary sequence-wise balance loss
  (arXiv:2412.19437 §2.1.2).

The router runs in float32. Dispatch drops no token: the token-choices are
sorted by held expert into a buffer of N·min(k, held) rows, which holds every
choice that can reach a held expert, and ``jax.lax.ragged_dot`` runs each
held expert over its group of rows. The weighted rows are scatter-added back
to their tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import ModelConfig, dense_init, mlp_apply, mlp_init

HIGHEST = jax.lax.Precision.HIGHEST


def init_moe(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 4)
    dt = cfg.jdtype
    E, D, F = cfg.num_experts, cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    _, held = cfg.held_experts

    def stack_init(key, d_in, d_out):
        keys = jax.random.split(key, held)
        return {"w": jnp.stack([dense_init(k, d_in, d_out, dt)["w"] for k in keys])}

    p = {
        "router": dense_init(ks[0], D, E, dt, scale=0.02),
        "wi": stack_init(ks[1], D, F),   # (held, D, F)
        "wg": stack_init(ks[2], D, F),
        "wo": stack_init(ks[3], F, D),   # (held, F, D)
    }
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), dt)
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(jax.random.split(ks[0])[0], cfg, d_ff=F * cfg.num_shared_experts)
    return p


def route(p: dict, cfg: ModelConfig, xt: jnp.ndarray):
    """xt: (N, D) → (scores (N, E) f32, chosen experts (N, k), weights (N, k) f32)."""
    logits = jnp.dot(xt.astype(jnp.float32), p["router"]["w"].astype(jnp.float32),
                     precision=HIGHEST)
    k = cfg.experts_per_token
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32), k)
    elif cfg.router_score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(scores, k)
    else:
        raise ValueError(f"unknown router_score {cfg.router_score!r}")
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / jnp.maximum(chosen.sum(-1, keepdims=True), 1e-9) * cfg.routed_scaling
    return scores, idx, weights


def _balance_loss(cfg: ModelConfig, scores, idx, batch: int) -> jnp.ndarray:
    E, k = cfg.num_experts, cfg.experts_per_token
    if cfg.router_score == "sigmoid":
        # per sequence: E/(k·T) Σ_t 1[i chosen at t] · (1/T) Σ_t s_it / Σ_j s_jt
        s = scores.reshape(batch, -1, E)
        T = s.shape[1]
        probs = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)          # (B, E)
        counts = jnp.sum(jax.nn.one_hot(idx.reshape(batch, T * k), E, dtype=jnp.float32),
                         axis=1)                                                   # (B, E)
        return jnp.mean(jnp.sum(counts * (E / (k * T)) * probs, axis=-1))
    # Switch: E · Σ_e (share of tokens whose first choice is e) · mean prob of e
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    return E * jnp.sum(frac * jnp.mean(scores, axis=0))


def _experts(p: dict, cfg: ModelConfig, xs: jnp.ndarray, group_sizes: jnp.ndarray, kept):
    """Each held expert's MLP over its group of the sorted rows ``xs``; the
    rows past the groups (``kept`` false) come out zero.

    The TPU's ragged dot leaves the rows of its output that lie in no group
    undefined, in the forward pass and in the transposes of the backward
    pass alike; every such row is zeroed before anything reads it, so that
    neither the output nor a gradient carries them."""
    keep = lambda t: jnp.where(kept[:, None], t, jnp.zeros((), t.dtype))
    xs = keep(xs)
    h = jax.lax.ragged_dot(xs, p["wi"]["w"], group_sizes)
    if cfg.activation in ("swiglu", "geglu"):
        g = jax.lax.ragged_dot(xs, p["wg"]["w"], group_sizes)
        act = jax.nn.silu if cfg.activation == "swiglu" else (lambda t: jax.nn.gelu(t, approximate=True))
        h = act(h) * g
    else:
        h = jax.nn.gelu(h, approximate=True)
    return keep(jax.lax.ragged_dot(keep(h), p["wo"]["w"], group_sizes))


def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """x: (B, S, D) → (out, stats, chosen experts (B·S, k)).

    ``stats``: ``aux``, the balance loss; ``moe_rows``, token-choices routed
    to held experts; ``moe_rows_alloc``, rows the grouped matmul was given;
    ``moe_load_max``, the largest held expert's rows over the held mean."""
    B, S, D = x.shape
    N, k = B * S, cfg.experts_per_token
    first, held = cfg.held_experts
    rows = N * min(k, held)
    xt = x.reshape(N, D)
    with jax.named_scope("moe_route"):
        scores, idx, weights = route(p, cfg, xt)
        aux = _balance_loss(cfg, scores, idx, B)
        local = idx.reshape(N * k) - first
        key = jnp.where((local >= 0) & (local < held), local, held)   # held: past the rest
        order = jnp.argsort(key, stable=True)[:rows]
        group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
        kept = key[order] < held
    with jax.named_scope("moe_experts"):
        token = order // k
        y = _experts(p, cfg, xt[token], group_sizes, kept)
        shared = mlp_apply(p["shared"], xt, cfg.activation) if "shared" in p else None
    with jax.named_scope("moe_combine"):
        w = jnp.where(kept, weights.reshape(N * k)[order], 0.0)
        out = jnp.zeros((N, D), jnp.float32).at[token].add(y.astype(jnp.float32) * w[:, None])
        if shared is not None:
            out = out + shared.astype(jnp.float32)
    n_rows = jnp.sum(group_sizes).astype(jnp.float32)
    stats = {
        "aux": aux,
        "moe_rows": n_rows,
        "moe_rows_alloc": jnp.float32(rows),
        "moe_load_max": jnp.max(group_sizes).astype(jnp.float32) * held
        / jnp.maximum(n_rows, 1.0),
    }
    return out.astype(x.dtype).reshape(B, S, D), stats, idx
