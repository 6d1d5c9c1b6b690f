#!/usr/bin/env python3
"""``control.py``'s control and planted fault for a cell that the
``train_moe`` driver runs: the expert model's float32 reference
(``reference.moe_lm``) against itself computed in float8, and against
itself given half of each batch, judged by the cell's own limits. The
benchmark's runs never run this.

    python3 bench/control_moe.py --workload <cell> --seeds 1 2 3

Prints one JSON line per seed with each number beside its limit; exits 1
when the control or the fault passes every limit on some seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402
from bench.control import judge  # noqa: E402


def train_controls(cell, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.drivers import train
    from bench.reference import lm, moe_lm as ref
    from repro.data import make_synthetic_wikitext

    arch = ref.Arch(cell.config)
    tf = cell.traffic
    opt = train.optimizer_of(tf)
    weights = jax.device_get(ref.make_weights(arch, seed))
    tokens = make_synthetic_wikitext(vocab_size=arch.vocab, seed=seed).train_tokens
    batches = train.first_batches(tokens, tf, seed)
    want = ref.train_reference(arch, weights, batches, opt)
    low = ref.train_reference(arch, weights, batches, opt, low=True)
    half = ref.train_reference(arch, weights, batches, opt, half_batch=True)

    peers = [jax.device_get(ref.make_weights(arch, seed, salt=i + 1))
             for i in range(tf["silos"] - 1)]
    fp8 = jax.jit(lm.fp8)
    worst = 0.0
    for path, x in weights.items():
        parts = [np.asarray(x, np.float64)] + [np.asarray(p[path], np.float64) for p in peers]
        mean = sum(parts) / len(parts)
        low_parts = [np.asarray(fp8(jnp.asarray(p, jnp.float32)), np.float64) for p in parts]
        got = lm.bf16_round((sum(low_parts) / len(low_parts)).astype(np.float32))
        want_agg = lm.bf16_round(mean.astype(np.float32))
        worst = max(worst, float(np.linalg.norm(got - want_agg))
                    / max(float(np.linalg.norm(mean)), 1e-30))
    return {"control": {**train.compare_steps(low, want), "agg_gap": worst},
            "half_batch": train.compare_steps(half, want),
            "reference_losses": want["losses"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    common.prepare_process()
    cell = common.load_cell(args.workload)
    if cell.traffic["driver"] != "train_moe":
        raise ValueError(f"this control is for driver 'train_moe', not {cell.traffic['driver']!r}")
    common.devices(cell.chips)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    passed = []
    for seed in args.seeds:
        out = train_controls(cell, seed)
        line = {"workload": args.workload, "seed": seed,
                "reference_losses": out["reference_losses"]}
        for name in ("control", "half_batch"):
            failed, line[name] = judge(cell, out[name])
            if not failed:
                passed.append((seed, name))
        print(json.dumps(line), flush=True)
    for seed, name in passed:
        print(f"control: {name} passed every limit on seed {seed}", file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
