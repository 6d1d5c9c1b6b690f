#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name under ``bench/workloads/``; its traffic names the
driver under ``bench/drivers/`` that sets it up, runs the measured window and
checks what the window produced. With ``--trace 0`` the last line of stdout
holds the cell's end-to-end metrics; with ``--trace 1`` the window is traced
and the line holds the per-layer metrics that the readers under
``bench/metrics/`` find in the run's record. Each number that decides
``correct`` is printed with its limit: as the last lines of stderr, and under
``checks``, the last key of the result's line.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import common  # noqa: E402


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             bench: Path = common.BENCH, require_tpu: bool = True) -> dict:
    """One run of cell ``name``; returns the result's line as a dict."""
    common.prepare_process()
    cell = common.load_cell(name, bench)
    driver = common.load_driver(cell)
    devs = common.devices(cell.chips, require_tpu=require_tpu)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    clock = common.CompileClock()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        out = driver.run(cell, seed=seed, seconds=seconds, trace_dir=trace_dir,
                         t_start=T_START, clock=clock)
        record = out["record"]
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
        if trace:
            from bench import trace as tr

            layout = tr.TPU if devs[0].platform == "tpu" else tr.cpu_layout()
            summary = tr.reduce(trace_dir, layout)
            record["trace"] = summary
            record["peaks"] = common.peaks_for(devs[0].device_kind) \
                if require_tpu else {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
            metrics = {}
            for mname, read in common.per_layer_readers().items():
                value = read(record)
                if value is not None:
                    metrics[mname] = value
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        else:
            metrics = {**out["e2e"], "setup_s": common.metric(out["setup_s"], "s")}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = out["checks"]
    seconds_c, compiles, hits = clock.read()
    result = {
        "correct": all(c.ok for c in checks),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {"device_ops": record["trace"].ops,
                               "idle_gaps": record["trace"].gaps}
    result["notes"] = {**out.get("notes", {}), "compile_s": seconds_c, "compiles": compiles,
                       "cache_hits": hits}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
