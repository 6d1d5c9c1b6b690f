"""Tests of the benchmark itself, on the CPU: ``python -m pytest bench/tests``.

They run the drivers at a tiny size from data files written to a temporary
directory; ``tiny_bench`` makes one."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = ROOT / "bench"


def _tiny_config() -> dict:
    c = json.loads((BENCH / "configs" / "granite-3-2b-4l.json").read_text())
    c.update(name="tiny", hidden_size=64, num_hidden_layers=2, num_attention_heads=2,
             num_key_value_heads=2, head_dim=32, intermediate_size=128, vocab_size=500,
             embedding_multiplier=8.0, attention_multiplier=32 ** -0.5,
             overrides={**c["overrides"], "n_layers": 2, "d_model": 64, "n_heads": 2,
                        "n_kv_heads": 2, "head_dim": 32, "d_ff": 128, "vocab_size": 500})
    return c


CELL = "granite-4l.silo-train"
# The tiny cell's limits, set as the granite cell's are but from readings at
# this size on the CPU: sound runs read `grad_gap` and `change_gap` up to about
# 5e-3 here, the float8 control and the half-batch fault 2e-2 and more.
TINY_LIMITS = {"loss_gap": 1.5e-05, "grad_gap": 0.01, "change_gap": 0.01, "agg_gap": 0.005}


@pytest.fixture(scope="session")
def tiny_bench(tmp_path_factory) -> Path:
    """A data directory outside the repository with a tiny configuration, a
    tiny train mix and the cell ``tiny.train``, held to ``TINY_LIMITS``."""
    root = tmp_path_factory.mktemp("bench_data")
    for sub in ("configs", "traffic", "workloads"):
        (root / sub).mkdir()
    (root / "configs" / "tiny.json").write_text(json.dumps(_tiny_config()))
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    train = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    train.update(batch=2, seq_len=32, steps_per_round=3, peer_period_s=0.05, trace_seconds=1.0)
    (root / "traffic" / "tiny-train.json").write_text(json.dumps(train))
    (root / "workloads" / "tiny.train.json").write_text(json.dumps(
        {"config": "tiny", "traffic": "tiny-train", "chips": 1, "why": "tests",
         "limits": TINY_LIMITS}))
    return root
