"""Moonlight-16B-A3B (DeepSeek-V3 block) against the plain float32 reference
``repro.models.deepseek_ref``, at ``reduced()`` size on the CPU, on seeded
weights with a non-zero correction bias.

Tolerances: both sides run float32 on the CPU and differ only in the order
of summation (sorted and grouped rows, chunked attention), so logits and loss
agree to 1e-5 relative; gradients, through one more pass of such sums, to
1e-4 of each leaf's norm. Rounding the program to bfloat16 moves the logits
by about 1e-2 relative, far outside either.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, deepseek_ref as ref
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.common import mlp_apply

B, S = 2, 32


def _cfg(**kw):
    return get_config("moonlight-16b-a3b").reduced().replace(**kw)


def _with_bias(params, key, std=0.05):
    """The correction bias drawn from ``key`` in place of its zero init."""
    def set_bias(path, leaf):
        if "router_bias" in jax.tree_util.keystr(path):
            return jax.random.normal(jax.random.fold_in(key, leaf.size), leaf.shape) * std
        return leaf
    return jax.tree_util.tree_map_with_path(set_bias, params)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def model_and_batch():
    cfg = _cfg()
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = _with_bias(model.init(key), key)
    batch = {"tokens": jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0, cfg.vocab_size)}
    return cfg, model, params, batch


def test_reduced_config_is_the_deepseek_block(model_and_batch):
    cfg, model, params, _ = model_and_batch
    assert model.head == ("attn",) and model.n_groups == 2 and not model.tail
    assert "wq" in params["head"]["h0_attn"]["attn"] and "wq_a" not in params["head"]["h0_attn"]["attn"]
    moe = params["blocks"]["u0_moe_attn"]["moe"]
    assert moe["router_bias"].shape == (2, cfg.num_experts)
    assert moe["wi"]["w"].shape == (2, cfg.num_experts, cfg.d_model, cfg.moe_d_ff)
    assert moe["shared"]["wi"]["w"].shape == (2, cfg.d_model, 2 * cfg.moe_d_ff)
    assert params["head"]["h0_attn"]["mlp"]["wi"]["w"].shape == (cfg.d_model, cfg.d_ff)
    assert "unembed" in params


def test_chip_share_has_the_published_parameter_count():
    """The benchmark's share: 1 dense + 4 expert layers, 8 of 64 experts,
    1/8 of the vocabulary (shapes only; nothing is allocated)."""
    cfg = get_config("moonlight-16b-a3b").replace(n_layers=5, experts_held=8, vocab_size=20480)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    assert count(shapes["head"]) == 82_973_184
    assert count(shapes["blocks"]) == 4 * 100_405_824
    assert count(shapes) == 568_484_608
    assert shapes["blocks"]["u0_moe_attn"]["moe"]["router"]["w"].shape == (4, 2048, 64)


@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_and_grads_match_reference(model_and_batch, remat):
    cfg, model, params, batch = model_and_batch
    logits, _ = model.apply(params, batch["tokens"], remat=remat)
    want, _, _ = ref.forward(cfg, params, batch["tokens"])
    assert _rel(logits[..., : cfg.vocab_size], want) < 1e-5

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch, remat=remat), has_aux=True)(params)
    want_loss, want_grads = jax.value_and_grad(lambda p: ref.loss(cfg, p, batch))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * abs(float(want_loss))
    assert float(metrics["aux"]) > 0
    flat_got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    flat_want = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    for path, g_want in flat_want:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects only: no gradient on either side
            assert float(jnp.max(jnp.abs(flat_got[path]))) == 0.0
            continue
        n_want = float(jnp.linalg.norm(g_want))
        assert n_want > 0, name
        assert abs(float(jnp.linalg.norm(flat_got[path])) - n_want) <= 1e-4 * n_want, name
        assert _rel(flat_got[path], g_want) < 1e-4, name


def _moe_params(cfg, key):
    p = moe_mod.init_moe(key, cfg)
    p["router_bias"] = jax.random.normal(jax.random.fold_in(key, 9), (cfg.num_experts,)) * 0.05
    return p


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight shares of 2 of 16 experts: their partial outputs, with the
    shared experts counted once, add up to the layer that holds all 16."""
    cfg = _cfg(num_experts=16, experts_per_token=4)
    key = jax.random.PRNGKey(3)
    p = _moe_params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    whole, stats, _ = moe_mod.moe_apply(p, cfg, x)
    want, want_aux, _ = ref.experts(p, cfg, x)
    assert _rel(whole, want) < 1e-5
    shared = mlp_apply(p["shared"], x.reshape(-1, cfg.d_model), cfg.activation).reshape(x.shape)
    total, rows = shared, 0.0
    for s in range(8):
        cfg_s = cfg.replace(experts_held=2, first_expert=2 * s)
        p_s = {**p, **{n: {"w": p[n]["w"][2 * s: 2 * s + 2]} for n in ("wi", "wg", "wo")}}
        part, stats_s, _ = moe_mod.moe_apply(p_s, cfg_s, x)
        assert float(stats_s["aux"]) == pytest.approx(float(stats["aux"]), rel=1e-6)
        total, rows = total + part - shared, rows + float(stats_s["moe_rows"])
    assert _rel(total, whole) < 1e-5
    assert rows == B * S * cfg.experts_per_token == float(stats["moe_rows"])
    assert float(stats["aux"]) == pytest.approx(float(want_aux), rel=1e-5)


@pytest.mark.parametrize("forced", [(4, 5, 6, 7), (5, 6)], ids=["all-held", "two-held"])
def test_dropless_when_every_token_routes_to_held_experts(forced):
    """A bias that sends every token's top-k to held experts: the layer still
    computes every choice (no capacity), and matches the reference."""
    k = len(forced)
    cfg = _cfg(num_experts=16, experts_per_token=k, experts_held=4, first_expert=4)
    key = jax.random.PRNGKey(4)
    p = _moe_params(cfg, key)
    p = {**p, **{n: {"w": p[n]["w"][:4]} for n in ("wi", "wg", "wo")}}
    p["router_bias"] = p["router_bias"].at[jnp.asarray(forced)].add(10.0)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    out, stats, idx = moe_mod.moe_apply(p, cfg, x)
    assert set(np.unique(np.asarray(idx)).tolist()) == set(forced)
    assert float(stats["moe_rows"]) == float(stats["moe_rows_alloc"]) == B * S * k
    # the forced experts take every token each: the load is even over them only
    assert float(stats["moe_load_max"]) == pytest.approx(4 / k)
    want, _, _ = ref.experts(p, cfg, x)
    assert _rel(out, want) < 1e-5


def test_selection_by_score_plus_bias_weights_from_score():
    cfg = _cfg(num_experts=8, experts_per_token=2, routed_scaling=2.446)
    key = jax.random.PRNGKey(5)
    p = _moe_params(cfg, key)
    p["router_bias"] = jnp.linspace(0.3, -0.3, cfg.num_experts)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B * S, cfg.d_model))
    scores, idx, weights = moe_mod.route(p, cfg, x)
    s = np.asarray(jax.nn.sigmoid(np.asarray(x, np.float64) @ np.asarray(p["router"]["w"], np.float64)))
    want_idx = np.argsort(-(s + np.asarray(p["router_bias"])), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(weights), chosen / chosen.sum(-1, keepdims=True) * 2.446,
                               rtol=1e-5)
    # the bias changed the choice for some tokens, and never entered the weights
    plain = np.argsort(-s, axis=-1)[:, :2]
    assert (np.sort(plain, -1) != np.sort(want_idx, -1)).any(axis=-1).mean() > 0.1
    out, _, _ = moe_mod.moe_apply(p, cfg, x.reshape(B, S, -1))
    want, _, _ = ref.experts(p, cfg, x.reshape(B, S, -1))
    assert _rel(out, want) < 1e-5


@pytest.mark.parametrize("seq", [48, 1536], ids=["dense", "chunked"])
def test_latent_attention_without_query_low_rank(seq):
    cfg = _cfg()
    assert cfg.q_lora_rank == 0
    key = jax.random.PRNGKey(6)
    p = attn_mod.init_mla(key, cfg)
    assert set(p) == {"wq", "wkv_a", "kv_a_norm", "wk_rope", "wk_b", "wv_b", "wo"}
    assert p["wq"]["w"].shape == (cfg.d_model, cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim))
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, seq, cfg.d_model))
    assert _rel(attn_mod.mla_full(p, cfg, x), ref.mla(p, cfg, x)) < 1e-5


def test_prefill_then_decode_matches_reference_forward(model_and_batch):
    cfg, model, params, batch = model_and_batch
    tokens = batch["tokens"][:, :16]
    want, _, _ = ref.forward(cfg, params, tokens)
    cache = model.init_cache(B, capacity=16)
    assert set(cache["head"]["h0_attn"]) == {"c_kv", "k_rope", "slot_pos"}
    logits, cache = model.prefill(params, tokens[:, :8], cache)
    got = [logits]
    step = jax.jit(model.decode_step)
    for t in range(8, 16):
        logits, cache = step(params, tokens[:, t], cache, jnp.int32(t))
        got.append(logits)
    for i, logits in zip(range(7, 16), got):
        assert _rel(logits[:, : cfg.vocab_size], want[:, i]) < 1e-4, i


def _ragged_dot_with_undefined_rows():
    """``jax.lax.ragged_dot`` whose output rows past the groups are NaN, in
    the forward pass and in the gradient of its left operand: the TPU leaves
    those rows undefined, where the CPU's dense form writes zeros."""
    real = jax.lax.ragged_dot

    def fill(out, group_sizes):
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    @jax.custom_vjp
    def ragged_dot(lhs, rhs, group_sizes):
        return fill(real(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return ragged_dot(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, ct):
        lhs, rhs, group_sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, group_sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(ct)
        return fill(d_lhs, group_sizes), d_rhs, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


def test_rows_past_the_groups_reach_no_output_or_gradient(monkeypatch):
    cfg = _cfg(num_experts=16, experts_per_token=4, experts_held=4, first_expert=8)
    key = jax.random.PRNGKey(7)
    p = _moe_params(cfg, key)
    p = {**p, **{n: {"w": p[n]["w"][:4]} for n in ("wi", "wg", "wo")}}
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, cfg.d_model))
    monkeypatch.setattr(jax.lax, "ragged_dot", _ragged_dot_with_undefined_rows())
    out, stats, _ = moe_mod.moe_apply(p, cfg, x)
    assert float(stats["moe_rows"]) < float(stats["moe_rows_alloc"])  # rows past the groups exist
    want, _, _ = ref.experts(p, cfg, x)
    assert _rel(out, want) < 1e-5
    f = lambda q, y: jnp.sum(moe_mod.moe_apply(q, cfg, y)[0] ** 2)
    f_ref = lambda q, y: jnp.sum(ref.experts(q, cfg, y)[0] ** 2)
    got, want = jax.grad(f, argnums=(0, 1))(p, x), jax.grad(f_ref, argnums=(0, 1))(p, x)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(g))), jax.tree_util.keystr(path)
        if "router_bias" not in jax.tree_util.keystr(path):
            assert _rel(g, w) < 1e-4, jax.tree_util.keystr(path)
