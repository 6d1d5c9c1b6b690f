"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed beside JAX, so the Pallas kernels (Mosaic) and
the jitted pythia-14m steps can be compiled here at their real shapes. That
catches what interpret mode cannot: layouts Mosaic refuses, primitives it
cannot lower, and programs that do not fit the chip's memory. Nothing runs,
so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file. Each compile runs with the persistent compilation cache off, since
an entry compiled for a described chip cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.fed_agg.kernel import fed_agg, fed_opt
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.launch.steps import make_bulk_prefill_step, make_serve_step
from repro.models import build_model
from repro.optim import adamw
from repro.training.trainer import make_train_step

V5E_HBM_BYTES = 16 * 1024**3

# pythia-14m serving shapes used by chip_smoke.py: B=4, 64-token prompt,
# 32 new tokens; training shapes are repro.launch.train's defaults
SERVE_B, PROMPT, NEW = 4, 64, 32
TRAIN_B, TRAIN_S = 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for(one_chip):
    """compile_for(fn, *shape_trees) → the compiled executable for one chip,
    with the persistent compilation cache off around the compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
        )

    def compile_(fn, *args):
        compiled = jax.jit(fn).lower(*[on_chip(a) for a in args]).compile()
        mem = compiled.memory_analysis()
        if mem is not None:
            used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
            assert used < V5E_HBM_BYTES, used
        return compiled

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ~1.4e7 elements: the flat of a model the size of the full pythia-14m (untied)
FLAT_N = 14_000_000


@pytest.mark.parametrize("K", [3, 100])
def test_fed_agg_compiles(compile_for, K):
    c = compile_for(lambda x, w: fed_agg(x, w), _sds((K, FLAT_N)), _sds((K,)))
    assert _has_kernel(c)


@pytest.mark.parametrize("K", [3, 100])
def test_fed_opt_compiles(compile_for, K):
    def step(x, w, p, m, v):
        return fed_opt(x, w, p, m, v, lr=1e-2, b1=0.9, b2=0.99, tau=1e-3)

    vec = _sds((FLAT_N,))
    c = compile_for(step, _sds((K, FLAT_N)), _sds((K,)), vec, vec, vec)
    assert _has_kernel(c)


# (arch, B, S, KV, G, hd): pythia-14m is MHA (G=1); granite-3-2b is GQA (G=4)
ATTN_SHAPES = [
    ("pythia-14m", 8, 128, 4, 1, 32),
    ("granite-3-2b", 1, 1024, 8, 4, 64),
]


@pytest.mark.parametrize("arch,B,S,KV,G,hd", ATTN_SHAPES, ids=[a[0] for a in ATTN_SHAPES])
def test_flash_attention_compiles(compile_for, arch, B, S, KV, G, hd):
    dt = get_config(arch).jdtype
    c = compile_for(
        lambda q, k, v: flash_attention(q, k, v),
        _sds((B, S, KV, G, hd), dt), _sds((B, S, KV, hd), dt), _sds((B, S, KV, hd), dt),
    )
    assert _has_kernel(c)


@pytest.mark.parametrize("arch,B,S,KV,G,hd", ATTN_SHAPES, ids=[a[0] for a in ATTN_SHAPES])
def test_decode_attention_compiles(compile_for, arch, B, S, KV, G, hd):
    dt = get_config(arch).jdtype
    C = 2048  # ring-cache slots
    c = compile_for(
        lambda q, k, v, valid: decode_attention(q, k, v, valid),
        _sds((B, KV, G, hd), dt), _sds((B, C, KV, hd), dt), _sds((B, C, KV, hd), dt),
        _sds((C,), jnp.bool_),
    )
    assert _has_kernel(c)


def test_ssd_scan_compiles(compile_for):
    cfg = get_config("mamba2-130m")
    H = cfg.d_model * cfg.ssm_expand // cfg.ssm_head_dim
    BH, S, P, N = 2 * H, 1024, cfg.ssm_head_dim, cfg.ssm_state
    c = compile_for(
        lambda x, a, b, cm: ssd_scan(x, a, b, cm, chunk=cfg.ssm_chunk),
        _sds((BH, S, P)), _sds((BH, S)), _sds((BH, S, N)), _sds((BH, S, N)),
    )
    assert _has_kernel(c)


@pytest.fixture(scope="module")
def pythia():
    cfg = get_config("pythia-14m")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return cfg, model, params


def test_pythia_train_step_compiles(compile_for, pythia):
    cfg, model, params = pythia
    assert cfg.vocab_size == 50304
    opt = adamw(2e-5)
    step = make_train_step(lambda p, b, rng: model.loss(p, b), opt)
    opt_state = jax.eval_shape(opt.init, params)
    batch = {"tokens": _sds((TRAIN_B, TRAIN_S), jnp.int32),
             "labels": _sds((TRAIN_B, TRAIN_S), jnp.int32)}
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compile_for(step, params, opt_state, batch, rng)


def test_pythia_serve_steps_compile(compile_for, pythia):
    cfg, model, params = pythia
    cache = jax.eval_shape(lambda: model.init_cache(SERVE_B, capacity=PROMPT + NEW))
    compile_for(make_bulk_prefill_step(cfg), params, _sds((SERVE_B, PROMPT), jnp.int32), cache)
    compile_for(make_serve_step(cfg), params, _sds((SERVE_B,), jnp.int32), cache,
                _sds((), jnp.int32))
