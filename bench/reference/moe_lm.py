"""Plain float32 reference of the DeepSeek-V3 decoder (``model_type``
``deepseek_v3``) that the Moonlight cell runs.

Written from the configuration file alone (its Hugging Face keys) and from no
code of the program (arXiv:2412.19437 §2.1):

- block: ``x += MLA(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``; FFN is a SwiGLU
  MLP in the first ``first_k_dense_replace`` layers and the expert layer in
  the rest;
- MLA with no query low-rank path: q = h·W_q, split into nope and rope parts;
  the latent c = RMSNorm(h·W_kv_a) and the rope key h·W_krope, which all heads
  share; k_nope = c·W_kb and v = c·W_vb; rotary (the two halves of each rope
  dimension paired) on the rope parts; causal softmax at 1/√(nope + rope);
- the expert layer: s = sigmoid(h·W_r) over all ``router_experts``; the top
  ``num_experts_per_tok`` of s + b are chosen (b, the correction bias, only
  selects); a chosen expert's gate is its s over the chosen s's sum, times
  ``routed_scaling_factor``, and 0 elsewhere. Each held expert (the
  ``n_routed_experts`` from ``first_held_expert``) runs on every token and is
  multiplied by its gate; the shared experts are added;
- the loss: mean next-token cross-entropy over the (sliced) vocabulary, plus
  ``aux_loss_alpha`` times DeepSeek-V3's sequence-wise balance loss of every
  expert layer, over all ``router_experts``.

Every contraction runs at ``Precision.HIGHEST``. To fit 568M float32
parameters with their gradient on one chip, each layer is checkpointed,
attention runs in query blocks, a batch runs one row at a time, and Adam's
moments and the starting weights stay on the host.

Parameters are a flat ``{path: array}`` dict laid out as the program lays
them out (the layer axis leads every stacked leaf); ``make_weights`` draws
them from the seed in one jitted call. ``low`` selects the float8 control of
``reference.lm``: every matmul operand rounded through ``fp8``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.lm import HIGHEST, VOCAB_PAD, _q, adamw_step, seed_key

# The untied embedding's standard deviation: rows of unit RMS, as the normed
# hidden states are (the model has no embedding multiplier).
EMBED_STD = 1.0
# The correction bias: the size the aux-free update (γ = 0.001 a step) gives
# it within a few thousand steps.
BIAS_STD = 0.01
QBLOCK = 512  # query rows per attention block

BLOCK = "blocks/u0_moe_attn/"


class Arch:
    """The sizes and constants of one ``deepseek_v3`` configuration file."""

    def __init__(self, cfg: dict):
        fixed = {"model_type": "deepseek_v3", "q_lora_rank": None, "scoring_func": "sigmoid",
                 "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "hidden_act": "silu",
                 "norm_topk_prob": True, "tie_word_embeddings": False, "moe_layer_freq": 1,
                 "num_nextn_predict_layers": 0, "attention_bias": False}
        for key, want in fixed.items():
            if cfg.get(key) != want:
                raise ValueError(f"the reference implements {key} = {want!r} only, "
                                 f"not {cfg.get(key)!r}")
        self.d = int(cfg["hidden_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.dense = int(cfg["first_k_dense_replace"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_rank = int(cfg["kv_lora_rank"])
        self.dn, self.dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
        self.dv = int(cfg["v_head_dim"])
        self.ff = int(cfg["intermediate_size"])
        self.moe_ff = int(cfg["moe_intermediate_size"])
        self.experts = int(cfg["router_experts"])
        self.held = int(cfg["n_routed_experts"])
        self.first = int(cfg["first_held_expert"])
        self.top_k = int(cfg["num_experts_per_tok"])
        self.shared = int(cfg["n_shared_experts"])
        self.scaling = float(cfg["routed_scaling_factor"])
        self.alpha = float(cfg["aux_loss_alpha"])
        self.vocab = int(cfg["vocab_size"])
        self.padded_vocab = -(-self.vocab // VOCAB_PAD) * VOCAB_PAD
        self.rope_theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        if not 0 <= self.first <= self.experts - self.held:
            raise ValueError("the held experts lie outside the router's")

    def param_shapes(self) -> dict[str, tuple[int, ...]]:
        d, H, R = self.d, self.heads, self.kv_rank
        block = {
            "norm1/scale": (d,), "norm2/scale": (d,),
            "attn/wq/w": (d, H * (self.dn + self.dr)),
            "attn/wkv_a/w": (d, R), "attn/kv_a_norm/scale": (R,),
            "attn/wk_rope/w": (d, self.dr),
            "attn/wk_b/w": (R, H * self.dn), "attn/wv_b/w": (R, H * self.dv),
            "attn/wo/w": (H * self.dv, d),
        }
        shapes = {"embed/table": (self.padded_vocab, d), "unembed/table": (self.padded_vocab, d),
                  "final_norm/scale": (d,)}
        for i in range(self.dense):
            dense = {**block, "mlp/wi/w": (d, self.ff), "mlp/wg/w": (d, self.ff),
                     "mlp/wo/w": (self.ff, d)}
            shapes.update({f"head/h{i}_attn/{k}": s for k, s in dense.items()})
        F, Fs, E, h = self.moe_ff, self.moe_ff * self.shared, self.experts, self.held
        moe = {**block, "moe/router/w": (d, E), "moe/router_bias": (E,),
               "moe/wi/w": (h, d, F), "moe/wg/w": (h, d, F), "moe/wo/w": (h, F, d),
               "moe/shared/wi/w": (d, Fs), "moe/shared/wg/w": (d, Fs),
               "moe/shared/wo/w": (Fs, d)}
        L = self.layers - self.dense
        shapes.update({BLOCK + k: (L,) + s for k, s in moe.items()})
        return dict(sorted(shapes.items()))

    def num_params(self) -> int:
        return sum(math.prod(s) for s in self.param_shapes().values())


def make_weights(arch: Arch, seed: int, salt: int = 0) -> dict[str, jax.Array]:
    """Seeded weights in the configuration's dtype, made on the default
    device in one jitted call: norm scales are ones, the embedding
    N(0, EMBED_STD²), the correction bias N(0, BIAS_STD²), the unembedding
    N(0, 1/hidden_size), any other matrix N(0, 1/fan_in). ``salt`` draws
    another set from the same seed."""
    return _draw(tuple(arch.param_shapes().items()), arch.dtype,
                 jax.random.fold_in(seed_key(seed), salt))


@partial(jax.jit, static_argnums=(0, 1))
def _draw(shapes, dtype, key):
    out = {}
    for i, (path, shape) in enumerate(shapes):
        if path.endswith("/scale"):
            out[path] = jnp.ones(shape, dtype)
            continue
        if path == "embed/table":
            std = EMBED_STD
        elif path.endswith("router_bias"):
            std = BIAS_STD
        elif path == "unembed/table":
            std = 1.0 / math.sqrt(shape[-1])
        else:
            std = 1.0 / math.sqrt(shape[-2])
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
        out[path] = w.astype(dtype)
    return out


# -- forward, one row ----------------------------------------------------------


def _mm(a, b, low: bool):
    return jnp.matmul(_q(a, low), _q(b, low), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, heads, d); the first and second halves of each rope dimension
    form the rotated pairs."""
    S, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(arch: Arch, q_nope, q_rope, k_nope, k_rope, v, low: bool):
    """Causal softmax attention, QBLOCK query rows at a time; each block is
    recomputed in the backward pass. q_*: (S, H, ·), k_rope: (S, dr)."""
    S = q_nope.shape[0]
    qb = QBLOCK if S % QBLOCK == 0 else S
    scale = 1.0 / math.sqrt(arch.dn + arch.dr)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * qb, qb)
        scores = (jnp.einsum("shd,thd->hst", _q(qn, low), _q(k_nope, low), precision=HIGHEST)
                  + jnp.einsum("shd,td->hst", _q(qr, low), _q(k_rope, low), precision=HIGHEST))
        qpos = i * qb + jnp.arange(qb)
        scores = jnp.where(kpos[None, None, :] <= qpos[None, :, None], scores * scale, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hst,thd->shd", _q(probs, low), _q(v, low), precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // qb))           # (blocks, qb, H, dv)
    return out.reshape(S, arch.heads * arch.dv)


def _mla(arch: Arch, p: dict, h, low: bool):
    S, H = h.shape[0], arch.heads
    q = _mm(h, p["attn/wq/w"], low).reshape(S, H, arch.dn + arch.dr)
    c = _rmsnorm(_mm(h, p["attn/wkv_a/w"], low), p["attn/kv_a_norm/scale"], arch.eps)
    k_rope = _rope(_mm(h, p["attn/wk_rope/w"], low)[:, None, :], arch.rope_theta)[:, 0]
    k_nope = _mm(c, p["attn/wk_b/w"], low).reshape(S, H, arch.dn)
    v = _mm(c, p["attn/wv_b/w"], low).reshape(S, H, arch.dv)
    q_nope, q_rope = q[..., : arch.dn], _rope(q[..., arch.dn:], arch.rope_theta)
    out = _attention(arch, q_nope, q_rope, k_nope, k_rope, v, low)
    return _mm(out, p["attn/wo/w"], low)


def _swiglu(wi, wg, wo, x, low: bool):
    return _mm(jax.nn.silu(_mm(x, wi, low)) * _mm(x, wg, low), wo, low)


def _experts(arch: Arch, p: dict, h, low: bool):
    """h: (S, D) of one row → (held experts' part + shared experts, the row's
    balance loss, chosen experts (S, k))."""
    S, E, k = h.shape[0], arch.experts, arch.top_k
    scores = jax.nn.sigmoid(_mm(h, p["moe/router/w"], low))                   # (S, E)
    _, idx = jax.lax.top_k(scores + p["moe/router_bias"].astype(jnp.float32), k)
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)                 # (S, E)
    gate = scores * chosen
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True) * arch.scaling
    held = jax.lax.dynamic_slice_in_dim(gate, arch.first, arch.held, axis=1)  # (S, held)

    def one(y, e):
        wi, wg, wo, g = e
        return y + g[:, None] * _swiglu(wi, wg, wo, h, low), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["moe/wi/w"], p["moe/wg/w"], p["moe/wo/w"], held.T))
    y = y + _swiglu(p["moe/shared/wi/w"], p["moe/shared/wg/w"], p["moe/shared/wo/w"], h, low)
    # sequence-wise balance: E/(k·S) · count_i, times the mean normalised score
    f = jnp.sum(chosen, axis=0) * E / (k * S)
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return y, jnp.sum(f * P), idx


def _layer(arch: Arch, low: bool, moe: bool, p: dict, x):
    x = x + _mla(arch, p, _rmsnorm(x, p["norm1/scale"], arch.eps), low)
    h = _rmsnorm(x, p["norm2/scale"], arch.eps)
    if moe:
        y, aux, idx = _experts(arch, p, h, low)
        return x + y, aux, idx
    return x + _swiglu(p["mlp/wi/w"], p["mlp/wg/w"], p["mlp/wo/w"], h, low), 0.0, None


def row_loss(arch: Arch, params: dict, tokens, labels, *, low: bool = False):
    """One row (S,) → (cross-entropy + α·balance loss, chosen experts of the
    expert layers (layers, S, k))."""
    x = jnp.take(_q(params["embed/table"], low), tokens, axis=0)
    aux, routes = 0.0, []
    for i in range(arch.dense):
        pre = f"head/h{i}_attn/"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x, _, _ = jax.checkpoint(partial(_layer, arch, low, False))(p, x)
    stacked = {k[len(BLOCK):]: v for k, v in params.items() if k.startswith(BLOCK)}
    for i in range(arch.layers - arch.dense):
        x, a, idx = jax.checkpoint(partial(_layer, arch, low, True))(
            {k: v[i] for k, v in stacked.items()}, x)
        aux, routes = aux + a, routes + [idx]
    x = _rmsnorm(x, params["final_norm/scale"], arch.eps)
    z = jnp.einsum("sd,vd->sv", _q(x, low), _q(params["unembed/table"][: arch.vocab], low),
                   precision=HIGHEST)
    ce = jnp.mean(jax.nn.logsumexp(z, axis=-1)
                  - jnp.take_along_axis(z, labels[:, None], axis=-1)[:, 0])
    return ce + arch.alpha * aux, jnp.stack(routes)


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def _accumulate(arch, low, params, acc, tokens, labels):
    """A row's loss and chosen experts, and ``acc`` + its gradient."""
    (l, routes), g = jax.value_and_grad(
        lambda p: row_loss(arch, p, tokens, labels, low=low), has_aux=True)(params)
    return l, jax.tree.map(jnp.add, acc, g), routes


@partial(jax.jit, static_argnums=(1,))
def _scaled(acc, n):
    return acc / n


def train_reference(arch: Arch, weights: dict, batches, opt: dict, *, low: bool = False,
                    half_batch: bool = False) -> dict:
    """The reference's first ``len(batches)`` steps from ``weights`` (host
    arrays), AdamW rounding the parameters to the configuration's dtype
    after each step.

    Returns each step's loss, the per-leaf norms of the first gradient and of
    the parameters' change over all the steps, and the experts each expert
    layer chose in the first step, (layers, rows·S, k). ``half_batch``
    plants a fault: each step sees only the first half of its rows."""
    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(w, jnp.float32) for k, w in weights.items()}
        m = {k: np.zeros(w.shape, np.float32) for k, w in weights.items()}
        v = {k: np.zeros(w.shape, np.float32) for k, w in weights.items()}
        losses, grad_norms, routes = [], None, None
        for i, b in enumerate(batches):
            tokens, labels = np.asarray(b["tokens"]), np.asarray(b["labels"])
            if half_batch:
                tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
            acc = jax.tree.map(jnp.zeros_like, params)
            total, step_routes = 0.0, []
            for r in range(len(tokens)):
                l, acc, rt = _accumulate(arch, low, params, acc, jnp.asarray(tokens[r]),
                                         jnp.asarray(labels[r]))
                total += float(l)
                step_routes.append(rt)
            if i == 0:
                routes = np.concatenate(jax.device_get(step_routes), axis=1)
            n = len(tokens)
            norms = {}
            for k in list(params):  # one leaf at a time: the moments live on the host
                g = _scaled(acc.pop(k), n)
                norms[k] = _norm(g)
                # one key for every leaf: the step compiles once per shape
                new, mk, vk = adamw_step(arch.dtype, {"x": params[k]}, {"x": g}, {"x": m[k]},
                                         {"x": v[k]}, jnp.float32(i), **opt)
                params[k] = new["x"]
                m[k], v[k] = np.asarray(mk["x"]), np.asarray(vk["x"])
            if i == 0:
                grad_norms = {k: float(x) for k, x in norms.items()}
            losses.append(total / n)
        del m, v
        change = {k: float(_norm(params[k] - jnp.asarray(w, jnp.float32)))
                  for k, w in weights.items()}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change, "routes": routes}


@jax.jit
def _norm(x):
    return jnp.linalg.norm(x.astype(jnp.float32).ravel())
