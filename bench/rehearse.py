#!/usr/bin/env python3
"""Compile each cell's device programs for a described TPU v5e chip, with no
chip attached, and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

For a train cell: ``Trainer``'s jitted step with the cell's optimizer at the
cell's batch. Prints one line per program with
``memory_analysis()``'s argument, output and temporary bytes. Nothing runs:
this says nothing about results or times.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402


def programs(cell):
    """(name, function, argument shapes) of the cell's device programs."""
    import jax
    import jax.numpy as jnp

    from bench.drivers.train import program_parts
    from repro.configs import get_config
    from repro.models import build_model
    from repro.training.trainer import make_train_step

    tf = cell.traffic
    cfg = get_config(cell.config["arch"]).replace(**cell.config.get("overrides", {}))
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if tf["driver"] != "train":
        raise ValueError(f"no programs known for driver {tf['driver']!r}")
    opt = program_parts(tf)[2]
    batch = {k: jax.ShapeDtypeStruct((tf["batch"], tf["seq_len"]), jnp.int32)
             for k in ("tokens", "labels")}
    step = make_train_step(lambda p, b, r: model.loss(p, b), opt)
    yield "train_step", step, (params, jax.eval_shape(opt.init, params), batch,
                               jax.eval_shape(lambda: jax.random.PRNGKey(0)))


def rehearse(cell) -> list[dict]:
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    one_chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    out = []
    for name, fn, args in programs(cell):
        on_chip = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), args)
        mem = jax.jit(fn).lower(*on_chip).compile().memory_analysis()
        row = {"cell": cell.name, "program": name,
               "argument_bytes": mem.argument_size_in_bytes,
               "output_bytes": mem.output_size_in_bytes,
               "temp_bytes": mem.temp_size_in_bytes}
        row["total_bytes"] = row["argument_bytes"] + row["output_bytes"] + row["temp_bytes"]
        out.append(row)
    return out


def main(argv=None) -> int:
    common.prepare_process()
    names = (argv if argv is not None else sys.argv[1:]) or sorted(
        p.stem for p in (common.BENCH / "workloads").glob("*.json"))
    for name in names:
        for row in rehearse(common.load_cell(name)):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
