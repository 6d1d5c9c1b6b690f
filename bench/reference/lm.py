"""Plain float32 reference of the decoder-only LM the cells run.

Written from the configuration file alone (its Hugging Face keys) and from no
code of the program: pre-norm RMSNorm blocks, grouped-query attention with
rotary positions (the two halves of each head rotated, NeoX order), a SwiGLU
or tanh-GELU feed-forward, a tied embedding that also unembeds, and Granite's
four multipliers (embedding, attention, residual, logits). Every contraction
runs at ``Precision.HIGHEST``.

Parameters are a flat ``{path: array}`` dict. The paths name the leaves as
the program lays them out, so that the benchmark can hand the same weights to
both; the layer axis leads every block leaf. ``make_weights`` draws them from
the seed, in the type the configuration states, in one jitted call.

``low`` selects the control: every matmul operand and the embedding rows are
first rounded to float8 e4m3 with a per-tensor scale (``fp8``), the precision
below the configuration's bfloat16 as a float8 training path would use it.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the largest magnitude that reduce_precision(exponent_bits=4, mantissa_bits=3)
# holds: a tensor is scaled so that its largest magnitude lands there
FP8_MAX = 240.0
VOCAB_PAD = 256  # rows of the embedding table are padded to a multiple of this
# The embedding's standard deviation, a tenth of the usual 0.02: with a tied
# embedding at 0.02 every greedy token of a random model repeats the one
# before it by a margin of about 25 logits, so no two precisions could be told
# apart by the tokens they serve.
EMBED_STD = 0.002


class Arch:
    """The sizes and constants of one configuration file."""

    def __init__(self, cfg: dict):
        if cfg.get("normalization", "rmsnorm") != "rmsnorm":
            raise ValueError("the reference implements RMSNorm blocks only")
        if cfg.get("use_parallel_residual", False) or cfg.get("rotary_pct", 1.0) != 1.0:
            raise ValueError("the reference implements sequential blocks with full rotary")
        if not cfg.get("tie_word_embeddings", True):
            raise ValueError("the reference implements a tied embedding only")
        self.d = int(cfg["hidden_size"])
        self.layers = int(cfg["num_hidden_layers"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg.get("num_key_value_heads", self.heads))
        self.head_dim = int(cfg.get("head_dim", self.d // self.heads))
        self.ff = int(cfg["intermediate_size"])
        self.vocab = int(cfg["vocab_size"])
        self.padded_vocab = -(-self.vocab // VOCAB_PAD) * VOCAB_PAD
        self.act = cfg["hidden_act"]
        if self.act not in ("silu", "gelu_pytorch_tanh"):
            raise ValueError(f"unsupported hidden_act {self.act!r}")
        self.rope_theta = float(cfg.get("rope_theta", cfg.get("rotary_emb_base", 10000.0)))
        self.eps = float(cfg.get("rms_norm_eps", cfg.get("layer_norm_eps")))
        self.emb_mult = float(cfg["embedding_multiplier"])
        self.attn_mult = float(cfg["attention_multiplier"])
        self.res_mult = float(cfg["residual_multiplier"])
        self.logits_scaling = float(cfg["logits_scaling"])
        self.dtype = jnp.dtype(cfg["torch_dtype"])

    def param_shapes(self) -> dict[str, tuple[int, ...]]:
        L, d, hd = self.layers, self.d, self.head_dim
        blk = "blocks/u0_attn/"
        shapes = {
            "embed/table": (self.padded_vocab, d),
            blk + "norm1/scale": (L, d),
            blk + "attn/wq/w": (L, d, self.heads * hd),
            blk + "attn/wk/w": (L, d, self.kv_heads * hd),
            blk + "attn/wv/w": (L, d, self.kv_heads * hd),
            blk + "attn/wo/w": (L, self.heads * hd, d),
            blk + "norm2/scale": (L, d),
            blk + "mlp/wi/w": (L, d, self.ff),
            blk + "mlp/wo/w": (L, self.ff, d),
            "final_norm/scale": (d,),
        }
        if self.act == "silu":
            shapes[blk + "mlp/wg/w"] = (L, d, self.ff)
        return dict(sorted(shapes.items()))

    def num_params(self) -> int:
        return sum(math.prod(s) for s in self.param_shapes().values())


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low and high 32 bits folded in
    (``PRNGKey`` itself silently truncates seeds past 32 bits)."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % 2**32)
    return jax.random.fold_in(key, (seed // 2**32) % 2**32)


def make_weights(arch: Arch, seed: int, salt: int = 0) -> dict[str, jax.Array]:
    """Seeded weights in the configuration's dtype, made on the default
    device in one jitted call: norm scales are ones, the embedding is
    N(0, EMBED_STD²), a matrix is N(0, 1/fan_in). ``salt`` draws another set
    from the same seed."""
    shapes = arch.param_shapes()
    return _draw(tuple(shapes.items()), arch.dtype, jax.random.fold_in(seed_key(seed), salt))


@partial(jax.jit, static_argnums=(0, 1))
def _draw(shapes, dtype, key):
    out = {}
    for i, (path, shape) in enumerate(shapes):
        if path.endswith("/scale"):
            out[path] = jnp.ones(shape, dtype)
            continue
        std = EMBED_STD if path == "embed/table" else 1.0 / math.sqrt(shape[-2])
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
        out[path] = w.astype(dtype)
    return out


# -- forward -------------------------------------------------------------------


def fp8(x):
    """``x`` (f32) as float8 e4m3 holds it under a per-tensor scale: scaled so
    that its largest magnitude is the format's largest, rounded to 4 exponent
    and 3 mantissa bits, scaled back; kept in f32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale


def _q(x, low: bool):
    """A matmul operand: f32, or for the control its value rounded through
    ``fp8`` (the gradient passes straight through, in f32)."""
    x = x.astype(jnp.float32)
    if not low:
        return x
    return x + jax.lax.stop_gradient(fp8(x) - x)


def _mm(a, b, low: bool):
    return jnp.matmul(_q(a, low), _q(b, low), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """x: (B, S, heads, hd); the first and second halves of each head form
    the rotated pairs."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv          # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(arch: Arch, p: dict, x, low: bool):
    B, S, _ = x.shape
    H, KV, hd = arch.heads, arch.kv_heads, arch.head_dim
    pos = jnp.arange(S)
    h = _rmsnorm(x, p["norm1/scale"], arch.eps)
    q = _mm(h, p["attn/wq/w"], low).reshape(B, S, H, hd)
    k = _mm(h, p["attn/wk/w"], low).reshape(B, S, KV, hd)
    v = _mm(h, p["attn/wv/w"], low).reshape(B, S, KV, hd)
    q, k = _rope(q, pos, arch.rope_theta), _rope(k, pos, arch.rope_theta)
    # query head j reads key/value head j // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", _q(q, low), _q(k, low), precision=HIGHEST)
    scores = scores * arch.attn_mult
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    a = jnp.einsum("bhst,bthd->bshd", _q(probs, low), _q(v, low), precision=HIGHEST)
    x = x + arch.res_mult * _mm(a.reshape(B, S, H * hd), p["attn/wo/w"], low)
    h = _rmsnorm(x, p["norm2/scale"], arch.eps)
    up = _mm(h, p["mlp/wi/w"], low)
    if arch.act == "silu":
        up = jax.nn.silu(up) * _mm(h, p["mlp/wg/w"], low)
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + arch.res_mult * _mm(up, p["mlp/wo/w"], low)


def logits(arch: Arch, params: dict, tokens, *, low: bool = False):
    """(B, S) tokens -> (B, S, vocab) f32 logits over the unpadded vocabulary."""
    table = _q(params["embed/table"], low)
    x = jnp.take(table, tokens, axis=0) * arch.emb_mult
    blk = "blocks/u0_attn/"
    names = [k[len(blk):] for k in params if k.startswith(blk)]
    for i in range(arch.layers):
        x = _layer(arch, {n: params[blk + n][i] for n in names}, x, low)
    x = _rmsnorm(x, params["final_norm/scale"], arch.eps)
    out = jnp.einsum("bsd,vd->bsv", _q(x, low), table[: arch.vocab], precision=HIGHEST)
    return out / arch.logits_scaling


def loss(arch: Arch, params: dict, tokens, labels, *, low: bool = False):
    """Mean next-token cross-entropy."""
    z = logits(arch, params, tokens, low=low)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@partial(jax.jit, static_argnums=(0, 4))
def _row_loss_grad(arch, params, tokens, labels, low):
    return jax.value_and_grad(lambda p: loss(arch, p, tokens, labels, low=low))(params)


def loss_and_grad(arch: Arch, params: dict, tokens, labels, *, low: bool = False):
    """Loss and gradient of a batch, one row at a time (rows have equal
    lengths, so the batch mean is the mean of the row means)."""
    total, grads = 0.0, None
    for r in range(tokens.shape[0]):
        l, g = _row_loss_grad(arch, params, tokens[r:r + 1], labels[r:r + 1], low)
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.shape[0]
    return total / n, jax.tree.map(lambda g: g / n, grads)


# -- AdamW, with the parameters stored in the configuration's dtype ----------


def round_to(dtype, x):
    """``x`` (f32) rounded to the nearest value of ``dtype``, kept in f32.
    ``reduce_precision`` is kept by XLA, where a convert to a narrower type
    and back may be simplified away."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


@partial(jax.jit, static_argnums=(0,), static_argnames=("lr", "b1", "b2", "eps", "wd"))
def adamw_step(dtype, params, grads, m, v, step, *, lr, b1, b2, eps, wd):
    """One decoupled-weight-decay Adam step (Loshchilov & Hutter); the new
    parameters are rounded to ``dtype``, the type they are stored in."""
    step = step + 1
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)

    def new(p, m_, v_):
        u = -lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) - lr * wd * p
        return round_to(dtype, p + u)

    return jax.tree.map(new, params, m, v), m, v


def train_reference(arch: Arch, weights: dict, batches, opt: dict, *, low: bool = False,
                    half_batch: bool = False) -> dict:
    """The reference's first ``len(batches)`` steps from ``weights``.

    Returns each step's loss, the per-leaf norms of the first gradient, and
    the per-leaf norms of the parameters' change over all the steps.
    ``half_batch`` plants a fault: each step sees only the first half of
    its rows."""
    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(w, jnp.float32) for k, w in weights.items()}
        start = params
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i, b in enumerate(batches):
            tokens, labels = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
            if half_batch:
                tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
            l, g = loss_and_grad(arch, params, tokens, labels, low=low)
            if i == 0:
                grad_norms = leaf_norms(g)
            params, m, v = adamw_step(arch.dtype, params, g, m, v, jnp.float32(i), **opt)
            losses.append(float(l))
        change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


@jax.jit
def _norms(tree):
    return {k: jnp.linalg.norm(jnp.asarray(x, jnp.float32).ravel()) for k, x in tree.items()}


def leaf_norms(tree: dict) -> dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(_norms(tree)).items()}


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back to float32 (round to nearest even), on the
    host."""
    return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(np.float32)
