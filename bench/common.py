"""What every driver of the benchmark shares: where things are, how a cell is
found by name, the compile clock, the device, and the result's line."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import threading
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".bench_jax_cache"


def prepare_process() -> None:
    """Put the program on the path and the compile cache in the checkout.
    Runs before JAX is imported; the program's ``use_compile_cache`` then
    takes this directory from the environment."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # every program, however quick to compile, is read back on the next run
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    # and none is evicted: a machine may cap the cache below one cell's programs
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def _load_json(kind: str, name: str, bench: Path) -> dict:
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


@dataclass
class Cell:
    """One workload: a configuration under a traffic mix."""

    name: str
    chips: int
    config: dict
    traffic: dict
    why: str = ""
    limits: dict = field(default_factory=dict)


def load_cell(name: str, bench: Path = BENCH) -> Cell:
    """A cell by its name: ``workloads/<name>.json`` names its configuration
    (``configs/<config>.json``) and its traffic (``traffic/<traffic>.json``);
    the traffic names the driver that runs it (``drivers/<driver>.py``).
    ``bench`` is where the three data files are looked up."""
    w = _load_json("workloads", name, bench)
    return Cell(name=name, chips=int(w["chips"]), why=w.get("why", ""),
                config=_load_json("configs", w["config"], bench),
                traffic=_load_json("traffic", w["traffic"], bench),
                limits=dict(w["limits"]))


def load_module(path: Path):
    """Import a file by path (names such as ``idle_share.train`` hold dots)."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(cell: Cell):
    return load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")


def per_layer_readers() -> dict:
    """Every per-layer metric's reader, by metric name."""
    return {p.name[:-3]: load_module(p).read
            for p in sorted((BENCH / "metrics").glob("*.py"))}


class CompileClock:
    """Sums backend compile seconds and counts persistent-cache hits, read
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def read(self) -> tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.compiles, self.hits


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def devices(chips: int, *, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


def memory_peak() -> int:
    """Peak bytes in use on the fullest device so far (0 where the backend
    does not say). Drivers read it after the window, before the reference."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def phases(telemetry) -> dict[str, list]:
    """The program's telemetry spans so far, per name: [count, seconds].
    (The span ring itself is drained by every ``obs/`` flush; these totals
    are not.)"""
    if not telemetry:
        return {}
    return {k: [v["count"], v["total_s"]] for k, v in telemetry.recorder.phase_stats().items()}


def phases_since(telemetry, before: dict) -> dict[str, list]:
    """Spans recorded since ``before = phases(telemetry)``."""
    out = {}
    for k, (n, s) in phases(telemetry).items():
        n0, s0 = before.get(k, [0, 0.0])
        if n > n0:
            out[k] = [n - n0, s - s0]
    return out


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


@dataclass
class Check:
    """One number compared, with its limit: the run is correct only if every
    check's value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit
