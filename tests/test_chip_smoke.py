"""chip_smoke.py: its contract without a chip, and its phases on the CPU at
reduced size (the chip run itself is ``python3 chip_smoke.py`` on a TPU)."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_accelerator(tmp_path, where):
    """No TPU, or no repo beside the script: non-zero exit and no result."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or '"ok"' not in lines[-1]


def test_phases_on_cpu_reduced(tmp_path):
    cfg = chip_smoke.get_config(chip_smoke.ARCH).reduced()
    cpu = jax.devices("cpu")[0]
    data = chip_smoke.make_synthetic_wikitext(vocab_size=cfg.vocab_size, train_tokens=24_000,
                                              test_tokens=2_000, seed=0)
    shards = chip_smoke.partition_sequence_dataset(data.train_tokens, chip_smoke.NODES)
    chip_smoke.first_step_phase(cfg, shards[0], cpu)
    uri = str(tmp_path / "store")
    members = chip_smoke.federated_phase(cfg, uri, shards, cpu)
    chip_smoke.serving_phase(cfg, uri, members[0])
