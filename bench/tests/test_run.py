"""The contract's last line, from tiny runs of the train driver on the CPU, and
the refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import common
from bench.run import run_cell

E2E = {"train_tokens_per_s", "setup_s"}
PER_LAYER = {"fed_step_ms", "store_ms", "aggregate_ms", "train_step_ms", "train_mfu",
             "idle_share.train"}
SPEC = json.loads((common.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("trace", [False, True])
def test_last_line(tiny_bench, trace):
    out = run_cell("tiny.train", seed=2**40 + 3, seconds=1.5, trace=trace, bench=tiny_bench,
                   require_tpu=False)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(out["device"])
    units = {m["name"]: m["unit"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    for name, m in out["metrics"].items():
        assert m["unit"] == units.get(name, m["unit"]) and isinstance(m["value"], float)
    if trace:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert {m["name"] for m in SPEC["per_layer"]} <= set(out["metrics"]) == PER_LAYER
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(out["breakdown"]["device_ops"]) <= 10
    else:
        assert set(out["metrics"]) == E2E
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "granite-4l.silo-train",
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(common.ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_fails_without_the_program(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
