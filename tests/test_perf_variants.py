"""§Perf optimization variants must be EXACTLY interchangeable with their
baselines (the hillclimbs trade roofline terms, never semantics):

  dropless grouped MoE dispatch == every expert on every token × its gate
                                   (fwd + grads)
  H2 absorbed MLA               == naive MLA         (fwd + grads)
  H3 dots remat policy          == full remat        (loss + grads)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import deepseek_ref
from repro.models import moe as moe_mod


@pytest.mark.parametrize("arch", ["grok-1-314b", "llama4-scout-17b-a16e"])
def test_dropless_dispatch_matches_dense_experts(arch):
    """The sorted, grouped dispatch against the plain form, in which each
    expert runs on every token and is multiplied by its gate (zero where not
    chosen), with the softmax router these configs publish. At 96 tokens a
    capacity of 1.25·k·N/E would have dropped tokens; nothing is dropped."""
    cfg = get_config(arch).reduced().replace(dtype="float32")
    rng = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(rng, cfg)
    x = jax.random.normal(rng, (2, 96, cfg.d_model)) * 0.5
    out, stats, _ = moe_mod.moe_apply(p, cfg, x)
    want, aux, _ = deepseek_ref.experts(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(abs(stats["aux"] - aux)) < 1e-6
    assert float(stats["moe_rows"]) == 2 * 96 * cfg.experts_per_token
    g_got = jax.grad(lambda q: moe_mod.moe_apply(q, cfg, x)[0].sum())(p)
    g_want = jax.grad(lambda q: deepseek_ref.experts(q, cfg, x)[0].sum())(p)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_absorbed_mla_matches_naive_full_path():
    cfg = get_config("minicpm3-4b").reduced()
    m_naive = build_model(cfg)
    m_abs = build_model(cfg.replace(mla_absorb=True))
    rng = jax.random.PRNGKey(1)
    params = m_naive.init(rng)
    # short (dense sdpa) and long (chunked) sequence paths
    for S in (48, 2048):
        tokens = jax.random.randint(rng, (1, S), 0, cfg.vocab_size)
        l1, _ = m_naive.apply(params, tokens)
        l2, _ = m_abs.apply(params, tokens)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-4, atol=2e-4)


def test_remat_policies_agree():
    cfg = get_config("granite-3-2b").reduced()
    rng = jax.random.PRNGKey(2)
    batch = {
        "tokens": jax.random.randint(rng, (2, 1536), 0, cfg.vocab_size),
        "labels": jax.random.randint(rng, (2, 1536), 0, cfg.vocab_size),
    }
    losses, grads = {}, {}
    for policy in ("full", "dots"):
        model = build_model(cfg.replace(remat_policy=policy))
        params = model.init(jax.random.PRNGKey(3))
        (losses[policy], _), grads[policy] = jax.value_and_grad(
            lambda p: model.loss(p, batch, remat=True), has_aux=True
        )(params)
    assert float(abs(losses["full"] - losses["dots"])) < 1e-5
    for a, b in zip(jax.tree.leaves(grads["full"]), jax.tree.leaves(grads["dots"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_probs_bf16_close_enough():
    """bf16 P·V is an approximation — bounded, not exact."""
    from repro.models import attention as A

    rng = jax.random.PRNGKey(4)
    k1, k2, k3 = jax.random.split(rng, 3)
    B, S, KV, G, hd = 1, 2048, 2, 2, 64
    q = jax.random.normal(k1, (B, S, KV, G, hd), jnp.bfloat16)
    k = jax.random.normal(k2, (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(k3, (B, S, KV, hd), jnp.bfloat16)
    ref = A.chunked_sdpa(q, k, v, causal=True, probs_bf16=False)
    fast = A.chunked_sdpa(q, k, v, causal=True, probs_bf16=True)
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - fast.astype(jnp.float32))))
    assert err < 5e-2, err
