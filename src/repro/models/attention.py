"""Attention variants: GQA/MQA (+qk_norm, sliding window, softcap) and MLA.

Two entry points per variant:
  * full-sequence causal (training / prefill) — optionally dispatching to the
    Pallas flash kernel on TPU (repro.kernels.flash_attention),
  * single-token decode against a fixed-capacity KV cache. The cache is a
    ring buffer of capacity C: full attention uses C = max_len, sliding-window
    attention uses C = window, which is what makes `long_500k` decode feasible
    for dense architectures.

MLA (multi-head latent attention, MiniCPM3/DeepSeek-V2) caches the compressed
latent (kv_lora_rank + rope_dim per token) instead of per-head K/V. The decode
path has a naive form (reconstruct K/V each step) and an *absorbed* form
(fold W_uk into the query and W_uv into the output projection) — the absorbed
form is a §Perf hillclimb in EXPERIMENTS.md.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import ModelConfig, apply_rope, dense_apply, dense_init, rmsnorm_apply, rmsnorm_init

NEG_INF = -1e30


def _maybe_softcap(scores: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap > 0:
        scores = jnp.tanh(scores / cap) * cap
    return scores


def _sdpa(q, k, v, mask, softcap: float) -> jnp.ndarray:
    """q: (B,S,KV,G,hd), k/v: (B,T,KV,hd), mask: (B,S,T) or (S,T)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bskgh,btkh->bkgst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    scores = _maybe_softcap(scores, softcap)
    if mask.ndim == 2:
        mask_b = mask[None, None, None]
    else:
        mask_b = mask[:, None, None]
    scores = jnp.where(mask_b, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.astype(v.dtype)


def chunked_sdpa(
    qg: jnp.ndarray,       # (B, S, KV, G, hd)
    k: jnp.ndarray,        # (B, T, KV, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    qblock: int = 256,
    probs_bf16: bool = False,
) -> jnp.ndarray:
    """Memory-bounded attention: lax.scan over query blocks, full softmax per
    row against (a slice of) K. Never materializes the S×T score matrix —
    the XLA-native analogue of flash attention, required for the 4k/32k
    full-sequence shapes. With a sliding ``window``, each q-block only reads
    a (window + qblock) K/V slice → FLOPs drop from O(S·T) to O(S·window).
    """
    B, S, KV, G, hd = qg.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    if S % qblock != 0:
        qblock = math.gcd(S, qblock) or S
    nblk = S // qblock
    qb = qg.reshape(B, nblk, qblock, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)

    use_slice = window > 0 and causal
    span = min(T, window + qblock) if use_slice else T

    def body(_, inp):
        blk_idx, qblk = inp
        q0 = blk_idx * qblock
        if use_slice:
            start = jnp.clip(q0 + qblock - span, 0, T - span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            kpos = start + jnp.arange(span)
        else:
            ks, vs = k, v
            kpos = jnp.arange(T)
        scores = jnp.einsum("bskgh,btkh->bkgst", qblk.astype(jnp.float32), ks.astype(jnp.float32)) * scale
        scores = _maybe_softcap(scores, softcap)
        qpos = q0 + jnp.arange(qblock)
        mask = jnp.ones((qblock, kpos.shape[0]), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if probs_bf16:
            out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(jnp.bfloat16), vs)
        else:
            out = jnp.einsum("bkgst,btkh->bskgh", probs, vs.astype(jnp.float32))
        return None, out.astype(v.dtype)

    # flash-style backward: recompute block scores/probs instead of saving
    # them as scan residuals (f32 (B,KV,G,qblk,T) per block would dominate HBM)
    body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (jnp.arange(nblk), qb))
    return outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, hd)


# sequences longer than this use the chunked path in attn_full
CHUNKED_THRESHOLD = 1024


def causal_mask(seq: int, window: int = 0, offset: int = 0) -> jnp.ndarray:
    """(S, T) causal mask; optional sliding window; offset for prefix caches."""
    qpos = jnp.arange(seq)[:, None] + offset
    kpos = jnp.arange(seq + offset)[None, :]
    mask = kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    return mask


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig) -> dict:
    hd = cfg.head_dim_
    dt = cfg.jdtype
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, dt),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, dt),
    }
    if cfg.lora_rank:
        # LoRA adapter on the q projection: B starts at zero so the adapter
        # is initially a no-op (standard LoRA init).
        ka = jax.random.fold_in(k1, 1)
        p["lora_a"] = dense_init(ka, cfg.d_model, cfg.lora_rank, dt)
        p["lora_b"] = {"w": jnp.zeros((cfg.lora_rank, cfg.n_heads * hd), dt)}
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dt)
        p["k_norm"] = rmsnorm_init(hd, dt)
    return p


def _project_qkv(p: dict, cfg: ModelConfig, x: jnp.ndarray, positions: jnp.ndarray):
    B, S, _ = x.shape
    hd = cfg.head_dim_
    q_flat = dense_apply(p["wq"], x)
    if "lora_a" in p:
        q_flat = q_flat + dense_apply(p["lora_b"], dense_apply(p["lora_a"], x))
    q = q_flat.reshape(B, S, cfg.n_heads, hd)
    k = dense_apply(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = dense_apply(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm_apply(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _full_causal_out(cfg: ModelConfig, qg, k, v, *, window: int, use_flash: bool):
    """Full-sequence causal dispatch shared by attn_full / attn_prefill."""
    S = qg.shape[1]
    if use_flash and cfg.attn_logit_softcap == 0:
        from repro.kernels.flash_attention import ops as flash_ops

        return flash_ops.flash_attention(qg, k, v, window=window)
    if S > CHUNKED_THRESHOLD:
        return chunked_sdpa(qg, k, v, causal=True, window=window,
                            softcap=cfg.attn_logit_softcap, qblock=cfg.attn_qblock,
                            probs_bf16=cfg.attn_probs_bf16)
    return _sdpa(qg, k, v, causal_mask(S, window), cfg.attn_logit_softcap)


def attn_full(
    p: dict, cfg: ModelConfig, x: jnp.ndarray, *, window: int | None = None,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Full-sequence causal attention (train / prefill)."""
    B, S, _ = x.shape
    window = cfg.sliding_window if window is None else window
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, S, cfg.n_kv_heads, G, cfg.head_dim_)
    out = _full_causal_out(cfg, qg, k, v, window=window, use_flash=use_flash)
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim_)
    return dense_apply(p["wo"], out)


def fill_ring(cache: dict, entries: dict, seq: int) -> dict:
    """Scatter a length-``seq`` prefix (positions 0..seq-1) into a ring cache.

    Only the last min(seq, capacity) positions survive — exactly the state a
    token-at-a-time decode loop would have left behind after wrapping.
    Restricting the scatter to those positions keeps slot indices unique, so
    the update never depends on duplicate-index ordering.
    """
    capacity = cache["slot_pos"].shape[0]
    keep = min(seq, capacity)
    pos = jnp.arange(seq - keep, seq, dtype=jnp.int32)
    slots = pos % capacity
    out = dict(cache)
    for name, val in entries.items():
        out[name] = cache[name].at[:, slots].set(val[:, seq - keep:].astype(cache[name].dtype))
    out["slot_pos"] = cache["slot_pos"].at[slots].set(pos)
    return out


def init_attn_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> dict:
    """Ring-buffer KV cache. ``capacity`` = window for sliding attention,
    = max_len for full attention."""
    dt = dtype or cfg.jdtype
    hd = cfg.head_dim_
    return {
        "k": jnp.zeros((batch, capacity, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((batch, capacity, cfg.n_kv_heads, hd), dt),
        "slot_pos": jnp.full((capacity,), -1, jnp.int32),  # global pos per slot
    }


def attn_decode(
    p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict, pos: jnp.ndarray,
    *, window: int | None = None, use_kernel: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B,1,D); pos: scalar global position."""
    B = x.shape[0]
    hd = cfg.head_dim_
    window = cfg.sliding_window if window is None else window
    positions = jnp.full((1, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, positions)
    C = cache["k"].shape[1]
    slot = pos % C
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0)),
        "slot_pos": jax.lax.dynamic_update_slice(cache["slot_pos"], positions[0], (slot,)),
    }
    valid = cache["slot_pos"] >= 0
    valid &= cache["slot_pos"] <= pos
    if window and window > 0:
        valid &= cache["slot_pos"] > pos - window
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, 1, cfg.n_kv_heads, G, hd)
    if use_kernel:
        from repro.kernels.decode_attention import ops as dec_ops

        out = dec_ops.decode_attention(qg, cache["k"], cache["v"], valid, softcap=cfg.attn_logit_softcap)
    else:
        out = _sdpa(qg, cache["k"], cache["v"], valid[None, None, :], cfg.attn_logit_softcap)
    out = out.reshape(B, 1, cfg.n_heads * hd)
    return dense_apply(p["wo"], out), cache


def attn_prefill(
    p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict,
    *, window: int | None = None, use_flash: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """``attn_full`` that also fills the decode ring cache — serving's bulk
    prefill. Equivalent to pushing the prompt through ``attn_decode`` one
    token at a time (same projections, same rope positions, same ring
    occupancy) at full-sequence matmul cost. ``cache`` must be fresh
    (``init_attn_cache``); positions start at 0."""
    B, S, _ = x.shape
    window = cfg.sliding_window if window is None else window
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, S, cfg.n_kv_heads, G, cfg.head_dim_)
    out = _full_causal_out(cfg, qg, k, v, window=window, use_flash=use_flash)
    out = dense_apply(p["wo"], out.reshape(B, S, cfg.n_heads * cfg.head_dim_))
    return out, fill_ring(cache, {"k": k, "v": v}, S)


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(rng, cfg: ModelConfig) -> dict:
    dt = cfg.jdtype
    ks = jax.random.split(rng, 8)
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        # query path: down-project → norm → up-project to per-head (nope+rope)
        p = {
            "wq_a": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dt),
            "q_a_norm": rmsnorm_init(cfg.q_lora_rank, dt),
            "wq_b": dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_dim, dt),
        }
    else:  # no low-rank query path (DeepSeek-V2-Lite, Moonlight): one projection
        p = {"wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * qk_dim, dt)}
    p.update({
        # kv path: shared compressed latent + shared rope key
        "wkv_a": dense_init(ks[2], cfg.d_model, cfg.kv_lora_rank, dt),
        "kv_a_norm": rmsnorm_init(cfg.kv_lora_rank, dt),
        "wk_rope": dense_init(ks[3], cfg.d_model, cfg.qk_rope_dim, dt),
        "wk_b": dense_init(ks[4], cfg.kv_lora_rank, cfg.n_heads * cfg.qk_nope_dim, dt),
        "wv_b": dense_init(ks[5], cfg.kv_lora_rank, cfg.n_heads * cfg.v_head_dim, dt),
        "wo": dense_init(ks[6], cfg.n_heads * cfg.v_head_dim, cfg.d_model, dt),
    })
    return p


def _mla_q(p, cfg, x, positions):
    B, S, _ = x.shape
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    if "wq" in p:
        q = dense_apply(p["wq"], x)
    else:
        q = dense_apply(p["wq_b"], rmsnorm_apply(p["q_a_norm"], dense_apply(p["wq_a"], x),
                                                 cfg.norm_eps))
    q = q.reshape(B, S, cfg.n_heads, qk_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim :]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    c_kv = rmsnorm_apply(p["kv_a_norm"], dense_apply(p["wkv_a"], x), cfg.norm_eps)
    k_rope = apply_rope(dense_apply(p["wk_rope"], x)[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_full_absorbed(p: dict, cfg: ModelConfig, x: jnp.ndarray, *, window: int | None = None) -> jnp.ndarray:
    """Absorbed-matmul MLA for the FULL-SEQUENCE path (§Perf hillclimb H2).

    The naive path expands the latent cache into per-head K (H·qk_nope) and
    V (H·v_dim) for all S positions — H× the HBM traffic of the latent
    itself. Here W_uk folds into the query (per-head latent queries) and
    W_uv into the output: attention scores and context are computed directly
    against the (S, kv_rank) latent, which is read once per q-block instead
    of H-sized expansions. Trades score FLOPs (dim 64+32 → 256+32 per pair)
    for an H× cut in K/V bytes — the right trade for a memory-bound shape.
    """
    B, S, _ = x.shape
    window = cfg.sliding_window if window is None else window
    positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)            # (B,S,H,dn), (B,S,H,dr)
    c_kv, k_rope = _mla_latent(p, cfg, x, positions)         # (B,S,R), (B,S,dr)
    H, R = cfg.n_heads, cfg.kv_lora_rank
    wk_b = p["wk_b"]["w"].reshape(R, H, cfg.qk_nope_dim)
    # fold W_uk into the query: per-head latent-space queries (B,S,H,R)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b.astype(q_nope.dtype))
    # unified "key" = [latent ; rope] shared across heads (MQA, kv=1)
    q_full = jnp.concatenate([q_lat, q_rope], axis=-1)       # (B,S,H,R+dr)
    k_full = jnp.concatenate([c_kv, k_rope], axis=-1)        # (B,S,R+dr)
    # score scale must match the naive path: 1/sqrt(qk_nope+qk_rope), but
    # chunked_sdpa scales by 1/sqrt(R+dr) — pre-scale q to compensate.
    fix = math.sqrt(R + cfg.qk_rope_dim) / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    qg = (q_full * fix).reshape(B, S, 1, H, R + cfg.qk_rope_dim)
    # context in latent space: pad the latent "values" to key width
    v_lat = jnp.pad(c_kv, ((0, 0), (0, 0), (0, cfg.qk_rope_dim)))[:, :, None, :]
    kk = k_full[:, :, None, :]                                # (B,S,1,R+dr)
    if S > CHUNKED_THRESHOLD:
        ctx = chunked_sdpa(qg, kk, v_lat, causal=True, window=window or 0,
                           qblock=cfg.attn_qblock, probs_bf16=cfg.attn_probs_bf16)
    else:
        ctx = _sdpa(qg, kk, v_lat, causal_mask(S, window), 0.0)
    ctx_lat = ctx.reshape(B, S, H, R + cfg.qk_rope_dim)[..., :R]
    wv_b = p["wv_b"]["w"].reshape(R, H, cfg.v_head_dim)
    out = jnp.einsum("bshr,rhd->bshd", ctx_lat, wv_b.astype(ctx_lat.dtype))
    return dense_apply(p["wo"], out.reshape(B, S, H * cfg.v_head_dim))


def mla_full(p: dict, cfg: ModelConfig, x: jnp.ndarray, *, window: int | None = None) -> jnp.ndarray:
    with jax.named_scope("mla"):
        if cfg.mla_absorb:
            return mla_full_absorbed(p, cfg, x, window=window)
        return _mla_full_naive(p, cfg, x, window=window)


def _mla_full_naive(p: dict, cfg: ModelConfig, x: jnp.ndarray, *, window: int | None) -> jnp.ndarray:
    B, S, _ = x.shape
    window = cfg.sliding_window if window is None else window
    positions = jnp.arange(S)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_latent(p, cfg, x, positions)
    H = cfg.n_heads
    k_nope = dense_apply(p["wk_b"], c_kv).reshape(B, S, H, cfg.qk_nope_dim)
    v = dense_apply(p["wv_b"], c_kv).reshape(B, S, H, cfg.v_head_dim)
    # unify nope+rope into one head_dim so the shared chunked path applies:
    # k_rope is shared across heads (MQA-style) → broadcast.
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)                     # (B,S,H,dn+dr)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, cfg.qk_rope_dim))], axis=-1
    )
    # pad v up to qk head_dim so sdpa shapes line up, slice after
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - cfg.v_head_dim)))
    qg = q_full.reshape(B, S, H, 1, qk_dim)
    if S > CHUNKED_THRESHOLD:
        out = chunked_sdpa(qg, k_full, vp, causal=True, window=window or 0,
                           qblock=cfg.attn_qblock, probs_bf16=cfg.attn_probs_bf16)
    else:
        out = _sdpa(qg, k_full, vp, causal_mask(S, window), 0.0)
    out = out.reshape(B, S, H, qk_dim)[..., : cfg.v_head_dim]
    return dense_apply(p["wo"], out.reshape(B, S, H * cfg.v_head_dim))


def init_mla_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> dict:
    dt = dtype or cfg.jdtype
    return {
        "c_kv": jnp.zeros((batch, capacity, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, capacity, cfg.qk_rope_dim), dt),
        "slot_pos": jnp.full((capacity,), -1, jnp.int32),
    }


def mla_decode(
    p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict, pos: jnp.ndarray,
    *, window: int | None = None, absorb: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """One-token MLA decode.

    naive (absorb=False): reconstruct per-head K/V from all cached latents —
      cost O(C · kv_rank · H·hd) matmuls per step.
    absorbed (absorb=True): score directly in the latent space by folding
      W_uk into the query (q_lat = q_nope @ W_uk^T per head) and W_uv into the
      output — cost O(C · (kv_rank + rope)) per head, no K/V materialization.
    """
    B = x.shape[0]
    window = cfg.sliding_window if window is None else window
    positions = jnp.full((1, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv_new, k_rope_new = _mla_latent(p, cfg, x, positions)
    C = cache["c_kv"].shape[1]
    slot = pos % C
    cache = {
        "c_kv": jax.lax.dynamic_update_slice(cache["c_kv"], c_kv_new, (0, slot, 0)),
        "k_rope": jax.lax.dynamic_update_slice(cache["k_rope"], k_rope_new, (0, slot, 0)),
        "slot_pos": jax.lax.dynamic_update_slice(cache["slot_pos"], positions[0], (slot,)),
    }
    valid = (cache["slot_pos"] >= 0) & (cache["slot_pos"] <= pos)
    if window and window > 0:
        valid &= cache["slot_pos"] > pos - window
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    ckv = cache["c_kv"].astype(jnp.float32)        # (B,C,R)
    krope = cache["k_rope"].astype(jnp.float32)    # (B,C,r)
    H = cfg.n_heads

    if absorb:
        wk_b = p["wk_b"]["w"].astype(jnp.float32).reshape(cfg.kv_lora_rank, H, cfg.qk_nope_dim)
        # fold W_uk into the query: per-head latent query (B,1,H,R)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32), wk_b)
        scores = jnp.einsum("bshr,bcr->bhsc", q_lat, ckv)
        scores += jnp.einsum("bshd,bcd->bhsc", q_rope.astype(jnp.float32), krope)
        scores = jnp.where(valid[None, None, None], scores * scale, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhsc,bcr->bshr", probs, ckv)  # latent-space context
        wv_b = p["wv_b"]["w"].astype(jnp.float32).reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
        out = jnp.einsum("bshr,rhd->bshd", ctx_lat, wv_b)
    else:
        k_nope = dense_apply(p["wk_b"], cache["c_kv"]).reshape(B, C, H, cfg.qk_nope_dim)
        v = dense_apply(p["wv_b"], cache["c_kv"]).reshape(B, C, H, cfg.v_head_dim)
        scores = jnp.einsum("bshd,bchd->bhsc", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
        scores += jnp.einsum("bshd,bcd->bhsc", q_rope.astype(jnp.float32), krope)
        scores = jnp.where(valid[None, None, None], scores * scale, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhsc,bchd->bshd", probs, v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, 1, H * cfg.v_head_dim)
    return dense_apply(p["wo"], out), cache


def mla_prefill(
    p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict,
    *, window: int | None = None,
) -> tuple[jnp.ndarray, dict]:
    """``mla_full`` that also fills the latent decode cache. The latent
    projection is recomputed for the ring fill — two thin matmuls
    (d_model → kv_rank / rope_dim), noise next to the attention itself."""
    out = mla_full(p, cfg, x, window=window)
    positions = jnp.arange(x.shape[1])[None, :]
    c_kv, k_rope = _mla_latent(p, cfg, x, positions)
    return out, fill_ring(cache, {"c_kv": c_kv, "k_rope": k_rope}, x.shape[1])
