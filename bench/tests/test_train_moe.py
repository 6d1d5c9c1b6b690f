"""The ``train_moe`` driver at a tiny size on the CPU: the contract's last
line with the expert layer's metrics, the control and the planted faults
judged by the tiny cell's limits, and the readers of the new metrics."""
import json

import numpy as np
import pytest

from bench import common, control_moe
from bench.control import judge
from bench.reference import moe_lm as ref
from bench.run import run_cell
from bench.trace import Summary

BENCH = common.BENCH
CELL = "moonlight-5l.silo-train-4k"
# The tiny cell's limits, set from readings at this size on the CPU (seeds
# 1–9 and 2**40 + 3): sound runs read `loss_gap` up to 1.1e-7, `grad_gap`
# 2.3e-7, `change_gap` 7.9e-6 and `agg_gap` 2.1e-3 (the placed float32
# parameters against the bf16-rounded mean); the float8 control reads at
# least 6.6e-3, 7.3e-2, 1.5e-2 and 2.7e-2, the half-batch fault 2.8e-3 and
# 0.43 on the first two.
TINY_LIMITS = {"loss_gap": 1.5e-05, "grad_gap": 0.01, "change_gap": 0.01, "agg_gap": 0.005}
# float32: at 64 tokens one routing choice that bfloat16 rounding flips moves
# the mean loss by about 1e-3, as much as the float8 control does; in float32
# the program's choices are the reference's.
TINY = dict(torch_dtype="float32", hidden_size=64, num_hidden_layers=3, num_attention_heads=2,
            num_key_value_heads=2, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32, router_experts=8,
            n_routed_experts=4, first_held_expert=4, num_experts_per_tok=2, vocab_size=500)
OVERRIDES = dict(n_layers=3, d_model=64, n_heads=2, n_kv_heads=2, kv_lora_rank=16, qk_nope_dim=16,
                 qk_rope_dim=8, v_head_dim=16, d_ff=128, moe_d_ff=32, num_experts=8,
                 experts_held=4, first_expert=4, experts_per_token=2, vocab_size=500,
                 dtype="float32")


@pytest.fixture(scope="module")
def tiny_moe_bench(tmp_path_factory):
    """A data directory outside the repository with the cell
    ``tiny.train-moe``: the Moonlight configuration at a tiny size (1 dense
    and 2 expert layers, 4 of 8 experts held from the fifth), 2 × 32 tokens,
    held to ``TINY_LIMITS``."""
    root = tmp_path_factory.mktemp("bench_moe_data")
    for sub in ("configs", "traffic", "workloads"):
        (root / sub).mkdir()
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json").read_text())
    config.update(name="tiny-moe", **TINY, overrides={**config["overrides"], **OVERRIDES})
    (root / "configs" / "tiny-moe.json").write_text(json.dumps(config))
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    traffic.update(batch=2, seq_len=32, steps_per_round=3, peer_period_s=0.05, trace_seconds=1.0)
    (root / "traffic" / "tiny-train-moe.json").write_text(json.dumps(traffic))
    (root / "workloads" / "tiny.train-moe.json").write_text(json.dumps(
        {"config": "tiny-moe", "traffic": "tiny-train-moe", "chips": 1, "why": "tests",
         "limits": TINY_LIMITS}))
    return root


def _run(bench, trace=False, seed=2**40 + 3):
    return run_cell("tiny.train-moe", seed=seed, seconds=1.5, trace=trace, bench=bench,
                    require_tpu=False)


@pytest.mark.parametrize("trace", [False, True])
def test_last_line(tiny_moe_bench, trace):
    out = _run(tiny_moe_bench, trace)
    assert out["correct"] is True and out["attempted"] > 0, out["checks"]
    assert set(out["checks"]) == set(TINY_LIMITS)
    notes = out["notes"]
    assert 0.0 <= notes["route_gap"] < 0.05
    # 2 expert layers × 64 tokens × 2 choices, of which those of experts 4–7
    assert 0 < notes["moe_rows"] <= notes["moe_rows_alloc"] == 2 * 64 * 2
    if trace:
        spec = json.loads((common.ROOT / "BENCHMARK.json").read_text())
        mine = {m["name"] for m in spec["per_layer"] if CELL in m.get("workloads", [CELL])}
        assert mine <= set(out["metrics"])
        assert {"moe_ms", "mla_ms", "moe_pad_share"} <= set(out["metrics"])
        assert 0 < out["metrics"]["moe_pad_share"]["value"] < 100
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    json.dumps(out)


@pytest.mark.parametrize("reading", ["control", "half_batch"])
def test_control_fails(tiny_moe_bench, reading):
    cell = common.load_cell("tiny.train-moe", tiny_moe_bench)
    for seed in (1, 2, 2**36 + 5):
        failed, checks = judge(cell, control_moe.train_controls(cell, seed)[reading])
        assert failed, checks


def test_half_of_the_batch_left_out(tiny_moe_bench, monkeypatch):
    from repro.training import trainer

    make = trainer.make_train_step

    def half(loss_fn, opt):
        step = make(loss_fn, opt)

        def s(params, opt_state, batch, rng):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state, {k: v[:n] for k, v in batch.items()}, rng)
        return s

    monkeypatch.setattr(trainer, "make_train_step", half)
    assert _run(tiny_moe_bench)["correct"] is False


def test_routing_without_the_bias_is_caught(tiny_moe_bench, monkeypatch):
    """A router that ignores the correction bias: a different expert layer."""
    from repro.models import moe

    route = moe.route

    def unbiased(p, cfg, xt):
        return route({**p, "router_bias": p["router_bias"] * 0}, cfg, xt)

    monkeypatch.setattr(moe, "route", unbiased)
    out = _run(tiny_moe_bench)
    assert out["correct"] is False and out["notes"]["route_gap"] > 0


def test_reference_layout_and_flops():
    cell = common.load_cell(CELL)
    arch = ref.Arch(cell.config)
    assert arch.num_params() == 568_484_608
    drv = common.load_driver(cell)
    B, S = cell.traffic["batch"], cell.traffic["seq_len"]
    base = drv.train_flops(arch, B, S, 0.0)
    # 6 per token per non-expert weight: 249,692,160 weights
    attn = 6.0 * 5 * 16 * (128 + 64 + 128) * S * B * S
    assert base == pytest.approx(6.0 * 249_692_160 * B * S + attn)
    rows = B * S * 6 / 8 * 4  # the mean a balanced router gives 8 of 64 experts
    assert drv.train_flops(arch, B, S, rows) - base == pytest.approx(6.0 * 3 * 2048 * 1408 * rows)


def test_route_gap():
    drv = common.load_driver(common.load_cell(CELL))
    a = np.array([[[0, 9], [1, 2]]])
    assert drv.route_gap(a, a, 0, 8) == 0.0
    b = np.array([[[0, 9], [1, 3]]])  # token 1 chose 3 for 2: two of six held choices differ
    assert drv.route_gap(a, b, 0, 8) == pytest.approx(2 / 6)
    assert drv.route_gap(a, b, 4, 4) == 0.0  # neither side chose 4–7


READERS = common.per_layer_readers()


def test_pad_share_reader():
    record = {"kind": "train", "moe_rows": 768.0, "moe_rows_alloc": 3072.0}
    assert READERS["moe_pad_share"](record) == {"value": 75.0, "unit": "%"}
    # silent where the program counts no routed rows (a dense cell, or the parent)
    assert READERS["moe_pad_share"]({"kind": "train", "rounds": 3}) is None


@pytest.mark.parametrize("metric,scope", [("moe_ms", "moe"), ("mla_ms", "mla")])
def test_scope_readers(metric, scope):
    summary = Summary(window_s=10.0, busy_s=5.0, devices=1,
                      modules={"jit_train_step": [20, 4.0]})
    record = {"kind": "train", "trace": summary, "scope_seconds": {scope: 1.0}}
    assert READERS[metric](record) == {"value": 50.0, "unit": "ms"}
    assert READERS[metric]({"kind": "train", "trace": summary}) is None
    assert READERS[metric]({**record, "scope_seconds": {scope: 0.0}}) is None
