"""Decoder-only LM assembly (all assigned archs except seamless-m4t).

Layers are grouped by the config's repeating ``pattern`` (e.g. RecurrentGemma's
("rglru","rglru","attn")); each pattern position has its params stacked over a
leading group axis and the whole stack is consumed by one ``jax.lax.scan`` —
a 64-layer grok-1 lowers to a single compact scanned HLO body. Leading dense
layers (``first_dense_layers``, DeepSeek-V3's ``first_k_dense_replace``) are
applied unstacked before the scan as a head, and a remainder
((n_layers − first_dense_layers) % len(pattern)) after it as a tail.

Block types:
  attn      — pre-norm attention (GQA or MLA) + pre-norm MLP
  moe_attn  — pre-norm attention (GQA or MLA) + pre-norm MoE (aux loss and
              routing counters accumulated)
  ssm       — pre-norm Mamba2 SSD block (no separate MLP)
  rglru     — pre-norm RG-LRU recurrent block + pre-norm MLP

The VLM (internvl2) prepends stub patch embeddings to the token embeddings;
only text positions produce logits/loss.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .common import (
    ModelConfig,
    embedding_apply,
    embedding_init,
    mlp_apply,
    mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
    softmax_cross_entropy,
    token_accuracy,
    unembed_apply,
)


# ---------------------------------------------------------------------------
# Per-block init / apply / cache
# ---------------------------------------------------------------------------


# Routing counters of the MoE blocks, accumulated over the layers of a
# forward pass: summed, except the load ratio, which keeps the largest layer's.
_SUMMED = ("aux", "moe_rows", "moe_rows_alloc")
MOE_STATS = _SUMMED + ("moe_load_max",)


def _zero_stats() -> dict:
    return {k: jnp.zeros((), jnp.float32) for k in MOE_STATS}


def _add_stats(total: dict, stats: dict) -> dict:
    if not stats:
        return total
    out = {k: total[k] + stats[k] for k in _SUMMED}
    out["moe_load_max"] = jnp.maximum(total["moe_load_max"], stats["moe_load_max"])
    return out


def _init_block(rng, cfg: ModelConfig, kind: str) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    attn_init = attn_mod.init_mla if cfg.attention == "mla" else attn_mod.init_attention
    if kind == "attn":
        return {
            "norm1": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "attn": attn_init(k1, cfg),
            "norm2": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "mlp": mlp_init(k2, cfg),
        }
    if kind == "moe_attn":
        return {
            "norm1": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "attn": attn_init(k1, cfg),
            "norm2": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "moe": moe_mod.init_moe(k2, cfg),
        }
    if kind == "ssm":
        return {
            "norm1": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "ssm": ssm_mod.init_mamba2(k1, cfg),
        }
    if kind == "rglru":
        return {
            "norm1": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "rglru": rglru_mod.init_rglru(k1, cfg),
            "norm2": rmsnorm_init(cfg.d_model, cfg.jdtype),
            "mlp": mlp_init(k2, cfg),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def _apply_block_full(p, cfg: ModelConfig, kind: str, x, *, window_override=None, use_flash=False):
    """Full-sequence forward. Returns (x, stats, route): an MoE block's
    routing counters and chosen experts (B·S, k); ({}, None) for others."""
    if kind in ("attn", "moe_attn"):
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        if cfg.attention == "mla":
            a = attn_mod.mla_full(p["attn"], cfg, h, window=window_override)
        else:
            a = attn_mod.attn_full(p["attn"], cfg, h, window=window_override, use_flash=use_flash)
        x = x + a
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            out, stats, route = moe_mod.moe_apply(p["moe"], cfg, h)
            return x + out, stats, route
        return x + mlp_apply(p["mlp"], h, cfg.activation), {}, None
    if kind == "ssm":
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        return x + ssm_mod.mamba2_full(p["ssm"], cfg, h), {}, None
    if kind == "rglru":
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        x = x + rglru_mod.rglru_full(p["rglru"], cfg, h)
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        return x + mlp_apply(p["mlp"], h, cfg.activation), {}, None
    raise ValueError(kind)


def _init_block_cache(cfg: ModelConfig, kind: str, batch: int, capacity: int):
    if kind in ("attn", "moe_attn"):
        window = cfg.sliding_window
        cap = min(capacity, window) if window else capacity
        if cfg.attention == "mla":
            return attn_mod.init_mla_cache(cfg, batch, cap)
        return attn_mod.init_attn_cache(cfg, batch, cap)
    if kind == "ssm":
        return ssm_mod.init_ssm_cache(cfg, batch)
    if kind == "rglru":
        return rglru_mod.init_rglru_cache(cfg, batch)
    raise ValueError(kind)


def _apply_block_decode(p, cfg: ModelConfig, kind: str, x, cache, pos, *, window_override=None):
    if kind in ("attn", "moe_attn"):
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        if cfg.attention == "mla":
            a, cache = attn_mod.mla_decode(p["attn"], cfg, h, cache, pos, window=window_override,
                                           absorb=cfg.mla_absorb)
        else:
            a, cache = attn_mod.attn_decode(p["attn"], cfg, h, cache, pos, window=window_override)
        x = x + a
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            out = moe_mod.moe_apply(p["moe"], cfg, h)[0]
        else:
            out = mlp_apply(p["mlp"], h, cfg.activation)
        return x + out, cache
    if kind == "ssm":
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        out, cache = ssm_mod.mamba2_decode(p["ssm"], cfg, h, cache)
        return x + out, cache
    if kind == "rglru":
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        out, cache = rglru_mod.rglru_decode(p["rglru"], cfg, h, cache)
        x = x + out
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        return x + mlp_apply(p["mlp"], h, cfg.activation), cache
    raise ValueError(kind)


def _apply_block_prefill(p, cfg: ModelConfig, kind: str, x, cache, *, window_override=None):
    """Full-sequence forward that also fills the block's decode cache —
    ``_apply_block_decode``'s contract ((x, cache) in/out) at
    ``_apply_block_full``'s cost. ``cache`` must be fresh."""
    if kind in ("attn", "moe_attn"):
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        if cfg.attention == "mla":
            a, cache = attn_mod.mla_prefill(p["attn"], cfg, h, cache, window=window_override)
        else:
            a, cache = attn_mod.attn_prefill(p["attn"], cfg, h, cache, window=window_override)
        x = x + a
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            out = moe_mod.moe_apply(p["moe"], cfg, h)[0]
        else:
            out = mlp_apply(p["mlp"], h, cfg.activation)
        return x + out, cache
    if kind == "ssm":
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        out, cache = ssm_mod.mamba2_prefill(p["ssm"], cfg, h, cache)
        return x + out, cache
    if kind == "rglru":
        h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        out, cache = rglru_mod.rglru_prefill(p["rglru"], cfg, h, cache)
        x = x + out
        h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        return x + mlp_apply(p["mlp"], h, cfg.activation), cache
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pattern = cfg.pattern
        U = len(self.pattern)
        rest = cfg.n_layers - cfg.first_dense_layers
        self.n_groups = rest // U
        self.head = ("attn",) * cfg.first_dense_layers
        self.tail = tuple(self.pattern[: rest % U])

    def _unstacked(self, part: str) -> list[tuple[str, str]]:
        """(key, kind) of the head's or the tail's blocks, in order."""
        kinds = self.head if part == "head" else self.tail
        return [(f"{part[0]}{i}_{kind}", kind) for i, kind in enumerate(kinds)]

    # -- init ----------------------------------------------------------------
    def init(self, rng) -> dict:
        cfg = self.cfg
        k_emb, k_layers, k_tail = jax.random.split(rng, 3)
        params = {"embed": embedding_init(k_emb, cfg.padded_vocab, cfg.d_model, cfg.jdtype)}
        for part, key in (("head", jax.random.fold_in(rng, 3)), ("tail", k_tail)):
            blocks = self._unstacked(part)
            if blocks:
                params[part] = {name: _init_block(jax.random.fold_in(key, i), cfg, kind)
                                for i, (name, kind) in enumerate(blocks)}
        blocks = {}
        for u, kind in enumerate(self.pattern):
            ks = jax.random.split(jax.random.fold_in(k_layers, u), self.n_groups)
            blocks[f"u{u}_{kind}"] = jax.vmap(lambda k, kind=kind: _init_block(k, cfg, kind))(ks)
        params["blocks"] = blocks
        params["final_norm"] = rmsnorm_init(cfg.d_model, cfg.jdtype)
        if not cfg.tie_embeddings:
            k_un = jax.random.fold_in(k_emb, 7)
            params["unembed"] = embedding_init(k_un, cfg.padded_vocab, cfg.d_model, cfg.jdtype)
        return params

    # -- embedding frontends ---------------------------------------------------
    def _embed(self, params, tokens, extra_embeds=None):
        cfg = self.cfg
        x = embedding_apply(params["embed"], tokens) * jnp.asarray(
            cfg.embed_scale or cfg.d_model**0.5, cfg.jdtype
        )
        if extra_embeds is not None:
            # VLM / audio-LM: prepend stub modality embeddings
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        return x

    # -- full forward ----------------------------------------------------------
    def forward(self, params, tokens, extra_embeds=None, *, window_override=None,
                remat: bool = False, use_flash: bool = False):
        """→ (logits (B,S_text,padded_vocab), stats, routes): the MoE blocks'
        counters (``MOE_STATS``, zeros without experts) and the experts each
        MoE layer chose, (MoE layers, B·S, k) in layer order (None without)."""
        cfg = self.cfg
        x = self._embed(params, tokens, extra_embeds)
        n_text = tokens.shape[1]
        kw = dict(window_override=window_override, use_flash=use_flash)
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)

        def unstacked(part, x, stats, routes):
            for key, kind in self._unstacked(part):
                block = lambda p, x, kind=kind: _apply_block_full(p, cfg, kind, x, **kw)
                if remat:
                    block = jax.checkpoint(block, policy=policy)
                x, s, r = block(params[part][key], x)
                stats = _add_stats(stats, s)
                if r is not None:
                    routes.append(r[None])
            return x, stats

        def group_body(carry, group_params):
            x, stats = carry
            group_routes = []
            for u, kind in enumerate(self.pattern):
                x, s, r = _apply_block_full(group_params[f"u{u}_{kind}"], cfg, kind, x, **kw)
                stats = _add_stats(stats, s)
                if r is not None:
                    group_routes.append(r)
            return (x, stats), (jnp.stack(group_routes) if group_routes else None)

        routes: list = []
        x, stats = unstacked("head", x, _zero_stats(), routes)
        body = jax.checkpoint(group_body, policy=policy) if remat else group_body
        (x, stats), scanned = jax.lax.scan(body, (x, stats), params["blocks"])
        if scanned is not None:  # (groups, MoE units, N, k) → layer order
            routes.append(scanned.reshape((-1,) + scanned.shape[2:]))
        x, stats = unstacked("tail", x, stats, routes)
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        x = x[:, -n_text:]  # only text positions produce logits (VLM prefix)
        logits = unembed_apply(params.get("unembed", params["embed"]), x)
        return logits, stats, (jnp.concatenate(routes) if routes else None)

    def apply(self, params, tokens, extra_embeds=None, *, window_override=None,
              remat: bool = False, use_flash: bool = False):
        """→ (logits (B,S_text,padded_vocab), aux_loss)."""
        logits, stats, _ = self.forward(params, tokens, extra_embeds, window_override=window_override,
                                        remat=remat, use_flash=use_flash)
        return logits, stats["aux"]

    def routes(self, params, tokens, extra_embeds=None):
        """The experts each MoE layer chose for each token: (MoE layers, B·S, k)."""
        return self.forward(params, tokens, extra_embeds)[2]

    # -- loss -------------------------------------------------------------------
    def loss(self, params, batch, rng=None, *, remat: bool = False, use_flash: bool = False):
        """Mean cross-entropy plus the weighted balance loss. The metrics of an
        MoE model add the routing counters: ``moe_rows``, ``moe_rows_alloc``
        (summed over layers) and ``moe_load_max`` (the largest layer's)."""
        cfg = self.cfg
        logits, stats, _ = self.forward(
            params, batch["tokens"], batch.get("embeds"), remat=remat, use_flash=use_flash
        )
        ce = softmax_cross_entropy(logits, batch["labels"], valid_vocab=cfg.vocab_size)
        loss = ce.mean() + cfg.router_aux_weight * stats["aux"]
        metrics = {"ce": ce.mean(), "aux": stats["aux"],
                   "accuracy": token_accuracy(logits, batch["labels"])}
        if cfg.num_experts:
            metrics.update({k: stats[k] for k in MOE_STATS if k != "aux"})
        return loss, metrics

    # -- decode -------------------------------------------------------------------
    def init_cache(self, batch: int, capacity: int, *, window_override=None) -> dict:
        cfg = self.cfg
        eff_cfg = cfg if window_override is None else cfg.replace(sliding_window=window_override)
        caches = {}
        for u, kind in enumerate(self.pattern):
            one = _init_block_cache(eff_cfg, kind, batch, capacity)
            caches[f"u{u}_{kind}"] = jax.tree.map(
                lambda l: jnp.broadcast_to(l[None], (self.n_groups,) + l.shape), one
            )
        for part in ("head", "tail"):
            blocks = self._unstacked(part)
            if blocks:
                caches[part] = {key: _init_block_cache(eff_cfg, kind, batch, capacity)
                                for key, kind in blocks}
        return caches

    def _through_cache(self, block_fn, params, x, cache):
        """Every layer in order, each through ``block_fn(p, kind, x, cache) →
        (x, cache)``: the head, the scanned groups, the tail."""
        new_cache = {}

        def unstacked(part, x):
            if self._unstacked(part):
                new_cache[part] = {}
            for key, kind in self._unstacked(part):
                x, new_cache[part][key] = block_fn(params[part][key], kind, x, cache[part][key])
            return x

        def group_body(x, scanned):
            group_params, group_cache = scanned
            out = {}
            for u, kind in enumerate(self.pattern):
                key = f"u{u}_{kind}"
                x, out[key] = block_fn(group_params[key], kind, x, group_cache[key])
            return x, out

        x = unstacked("head", x)
        scan_cache = {k: v for k, v in cache.items() if k not in ("head", "tail")}
        x, scanned = jax.lax.scan(group_body, x, (params["blocks"], scan_cache))
        new_cache.update(scanned)
        x = unstacked("tail", x)
        return x, new_cache

    def decode_step(self, params, token, cache, pos, *, window_override=None):
        """token: (B,) int32; pos: scalar int32 → (logits (B,padded_vocab), cache)."""
        cfg = self.cfg
        x = self._embed(params, token[:, None])
        x, new_cache = self._through_cache(
            lambda p, kind, x, c: _apply_block_decode(p, cfg, kind, x, c, pos,
                                                      window_override=window_override),
            params, x, cache)
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        logits = unembed_apply(params.get("unembed", params["embed"]), x[:, 0])
        return logits, new_cache

    def prefill(self, params, tokens, cache, extra_embeds=None, *, window_override=None):
        """Bulk prefill: one full-sequence pass that fills a *fresh* decode
        cache (``init_cache``) and returns the last position's logits — the
        serving replacement for feeding a prompt through ``decode_step`` one
        token at a time. Positions start at 0 (the VLM prefix, if any,
        occupies positions 0..P-1). → (logits (B, padded_vocab), cache)."""
        cfg = self.cfg
        x = self._embed(params, tokens, extra_embeds)
        x, new_cache = self._through_cache(
            lambda p, kind, x, c: _apply_block_prefill(p, cfg, kind, x, c,
                                                       window_override=window_override),
            params, x, cache)
        x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        logits = unembed_apply(params.get("unembed", params["embed"]), x[:, -1])
        return logits, new_cache
