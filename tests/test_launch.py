"""Launch-layer units: sharding rules/resolver, input specs, cost model,
collective-bytes parser. (The full 512-device dry-run runs via
repro.launch.dryrun, not pytest — no XLA_FLAGS here.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch import costs as C
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import param_spec, resolve
from repro.launch.specs import SHAPES, cache_specs, decode_window_override, input_specs, params_specs


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def test_param_spec_rules():
    assert param_spec("blocks/u0_attn/attn/wq/w", 3, fsdp=False, dp="data") == P(None, None, "model")
    assert param_spec("blocks/u0_attn/attn/wo/w", 3, fsdp=False, dp="data") == P(None, "model", None)
    assert param_spec("blocks/u0_attn/attn/wo/w", 3, fsdp=True, dp="data") == P(None, "model", "data")
    assert param_spec("embed/table", 2, fsdp=False, dp="data") == P("model", None)
    assert param_spec("blocks/u0_moe_attn/moe/wi/w", 4, fsdp=True, dp="data") == P(None, None, "data", "model")
    assert param_spec("blocks/u0_moe_attn/moe/router/w", 3, fsdp=True, dp="data") == P()
    assert param_spec("final_norm/scale", 1, fsdp=True, dp="data") == P()
    # optimizer moments embed the param path → same rule applies
    assert param_spec("m/blocks/u0_attn/attn/wq/w", 3, fsdp=False, dp="data") == P(None, None, "model")


def test_resolver_drops_non_divisible():
    mesh = make_host_mesh(model_axis=1)  # (1 device) — degenerate but exercises logic
    s = resolve(P("data", "model"), (3, 5), mesh)
    # 3 % 1 == 0 → kept ("data" of size 1); same for model
    assert s.spec == P("data", "model")


def test_resolver_fallback_tuple_axis():
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    s = resolve(P(("pod", "data"),), (7,), jax.make_mesh((1, 1, 1), ("pod", "data", "model")))
    assert s.spec[0] in (("pod", "data"), "data", None)


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_token_budget(arch, shape_name):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    if shape.kind == "decode":
        assert specs["token"].shape == (shape.global_batch,)
        return
    total = specs["tokens"].shape[1]
    if cfg.is_encdec:
        total += specs["frames"].shape[1]
    elif "embeds" in specs:
        total += specs["embeds"].shape[1]
    assert total == shape.seq_len
    assert specs["tokens"].shape[0] == shape.global_batch


def test_cache_specs_long_context_window():
    cfg = get_config("qwen3-14b")
    shape = SHAPES["long_500k"]
    assert decode_window_override(cfg, shape) == cfg.long_context_window
    cache = cache_specs(cfg, shape)
    k = cache["u0_attn"]["k"]
    assert k.shape[2] == cfg.long_context_window  # ring capacity = window, not 500k


def test_cache_specs_ssm_state_only():
    cfg = get_config("mamba2-130m")
    cache = cache_specs(cfg, SHAPES["long_500k"])
    assert set(cache["u0_ssm"].keys()) == {"conv", "ssm"}


def test_params_specs_no_allocation():
    cfg = get_config("grok-1-314b")
    shapes = params_specs(cfg)  # eval_shape: would OOM instantly if real
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert 250e9 < n < 400e9  # ~314B params


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_jaxpr_costs_counts_matmul_exactly():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    costs = C.jaxpr_costs(f, a, b)
    assert costs.flops == 2 * 64 * 128 * 32


def test_jaxpr_costs_multiplies_scan_trips():
    def scanned(x, w):
        def body(c, wi):
            return c @ wi, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 32, 32), jnp.float32)
    costs = C.jaxpr_costs(scanned, x, w)
    assert costs.flops == 10 * 2 * 32**3  # trip-count aware (XLA reports 1/10th)


def test_jaxpr_costs_sees_through_remat_and_grad():
    def f(w, x):
        body = jax.checkpoint(lambda h, wi: (jnp.tanh(h @ wi), None))
        h, _ = jax.lax.scan(body, x, w)
        return jnp.sum(h)

    w = jax.ShapeDtypeStruct((4, 16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    fwd = C.jaxpr_costs(f, w, x).flops
    bwd = C.jaxpr_costs(lambda w, x: jax.grad(f)(w, x), w, x).flops
    assert fwd == 4 * 2 * 16**3
    assert bwd >= 2.5 * fwd  # fwd + remat recompute + 2-matmul backward


def test_collective_bytes_parser():
    hlo = """
body.1 (arg: f32[8]) -> f32[8] {
  %x = f32[1024,256]{1,0} all-reduce(%y), replica_groups=[]
}

ENTRY %main () -> f32[8] {
  %z = bf16[512]{0} all-gather(%w), channel_id=1
  %t = (f32[16,16]{1,0}, f32[16,16]{1,0}) all-to-all(%a, %b)
}
"""
    out = C.collective_bytes(hlo, loop_trip_count=10.0)
    assert out["all-reduce"] == 1024 * 256 * 4 * 10  # loop body × trips
    assert out["all-gather"] == 512 * 2               # ENTRY × 1
    assert out["all-to-all"] == 2 * 16 * 16 * 4
    assert out["total"] == out["all-reduce"] + out["all-gather"] + out["all-to-all"]


def test_roofline_terms_bottleneck():
    t = C.roofline_terms(total_flops=1e15, total_bytes=1e12, coll_bytes=1e10, chips=256)
    assert t["bottleneck"] == "compute_s"
    t2 = C.roofline_terms(total_flops=1e12, total_bytes=1e14, coll_bytes=0.0, chips=256)
    assert t2["bottleneck"] == "memory_s"


# ---------------------------------------------------------------------------
# the training launcher and the compile cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("failing,extra,vocab,rc", [
    (None, [], 50304, 0),                   # the config's own vocab by default
    ("node1", [], 50304, 1),                # a failed silo fails the run
    (None, ["--vocab", "512"], 512, 0),     # an explicit cut still applies
])
def test_train_simulate_vocab_and_exit_code(monkeypatch, failing, extra, vocab, rc):
    from repro.launch import train

    seen = {}

    def fake_client(cfg, node_id, index, folder, args, tokens_shard, eval_tokens):
        seen[node_id] = (cfg.vocab_size, index)
        if node_id == failing:
            raise RuntimeError("injected")
        return {"node": node_id}

    monkeypatch.setattr(train, "run_client", fake_client)
    monkeypatch.setattr(train, "use_compile_cache", lambda: None)
    assert train.main(["--arch", "pythia-14m", "--simulate", "2", *extra]) == rc
    assert seen == {"node0": (vocab, 0), "node1": (vocab, 1)}


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, from_env):
    from pathlib import Path

    from repro.launch.compile_cache import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        where = use_compile_cache()
        if from_env:
            # JAX reads the variable itself: nothing is set in code
            assert where == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            repo_root = Path(__file__).resolve().parents[1]
            assert where == str(repo_root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
