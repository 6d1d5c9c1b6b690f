"""The reduction from a profiler trace to numbers.

``record(dir)`` traces a window with JAX's profiler (Python tracer off, host
annotations on). ``reduce(dir, layout)`` reads the newest ``.xplane.pb`` under
``dir`` with ``jax.profiler.ProfileData`` and returns a :class:`Summary`:

- the window: the span of the ``bench.window`` annotation;
- busy seconds: per device, the union of the intervals in which an operation
  ran, clipped to the window, averaged over the devices;
- per jitted program (the "module" line): executions and device seconds;
- per operation name: device seconds, for the ten largest;
- idle gaps: the stretches of the window in which no device was busy, each
  named by the innermost ``bench.*`` host annotation that covers its middle.

Which planes and lines hold the device's work depends on the backend, so the
reduction takes a :class:`Layout`; ``TPU`` is the one the benchmark uses.
"""
from __future__ import annotations

import contextlib
import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"
HOST_PREFIX = "bench."


@dataclass(frozen=True)
class Layout:
    device_plane: str          # prefix of the planes that are devices
    op_line: str               # prefix of the lines, on a device plane, of single operations
    module_line: str           # prefix of the line of whole jitted programs
    host_plane: str = "/host:CPU"
    module_plane: str = ""     # plane of the module line ("" = the device plane)


TPU = Layout(device_plane="/device:TPU:", op_line="XLA Ops", module_line="XLA Modules")


def cpu_layout() -> Layout:
    """The CPU backend runs operations on its ``tf_XLA*`` thread lines and
    names each jitted call ``PjitFunction(<name>)`` on the Python thread's
    line; for tests of the reduction only."""
    return Layout(device_plane="/host:CPU", op_line="tf_XLA", module_line="python",
                  module_plane="/host:CPU")


@dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    modules: dict = field(default_factory=dict)      # name -> [count, seconds]
    ops: list = field(default_factory=list)          # [[name, seconds]], largest first
    gaps: list = field(default_factory=list)         # [[name, seconds]], longest first
    host: dict = field(default_factory=dict)         # annotation -> [count, seconds]

    def module(self, part: str) -> tuple[int, float]:
        """Executions and device seconds of the programs whose name holds
        ``part``."""
        n, s = 0, 0.0
        for name, (c, t) in self.modules.items():
            if part in name:
                n, s = n + c, s + t
        return n, s


@contextlib.contextmanager
def record(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


class Tracer:
    """Traces the first ``seconds`` of a window into ``log_dir`` (nothing
    when ``log_dir`` is None): ``start()`` at the window's start, ``poll()``
    as it runs, ``close()`` at its end."""

    def __init__(self, log_dir: str | None, seconds: float):
        self.log_dir, self.seconds = log_dir, seconds
        self._stack = contextlib.ExitStack()
        self._t0 = 0.0

    def start(self, now: float) -> None:
        self._t0 = now
        if self.log_dir:
            self._stack.enter_context(record(self.log_dir))

    def poll(self, now: float) -> None:
        if now - self._t0 >= self.seconds:
            self.close()

    def close(self) -> None:
        self._stack.close()


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps_of(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi) that ``busy`` (merged, sorted) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def newest_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def reduce(log_dir: str, layout: Layout = TPU, *, top: int = 10) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(log_dir))
    planes = list(data.planes)
    host = [p for p in planes if p.name == layout.host_plane]
    annotations = [ev for p in host for line in p.lines for ev in _events(line)
                   if ev[0].startswith(HOST_PREFIX)]
    windows = [(s, e) for n, s, e in annotations if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    lo, hi = windows[-1]

    dev_planes = [p for p in planes if p.name.startswith(layout.device_plane)]
    busy_total, merged_all, ops = 0.0, [], {}
    for p in dev_planes:
        spans = []
        for line in p.lines:
            if not line.name.startswith(layout.op_line):
                continue
            for name, s, e in _events(line):
                if e > lo and s < hi:
                    spans.append((s, e))
                    ops[name] = ops.get(name, 0.0) + min(e, hi) - max(s, lo)
        merged = union(spans, lo, hi)
        busy_total += sum(e - s for s, e in merged)
        merged_all.extend(merged)

    mod_planes = ([p for p in planes if p.name == layout.module_plane]
                  if layout.module_plane else dev_planes)
    modules: dict[str, list] = {}
    for p in mod_planes:
        for line in p.lines:
            if not line.name.startswith(layout.module_line):
                continue
            ends: dict[str, float] = {}  # an event nested in one of its name is not counted
            for name, s, e in sorted(_events(line), key=lambda ev: ev[1]):
                if lo <= s < hi and s >= ends.get(name, -1.0):
                    m = modules.setdefault(name, [0, 0.0])
                    m[0] += 1
                    m[1] += e - s
                    ends[name] = e

    host_spans = [(n, s, e) for n, s, e in annotations if n != WINDOW and e > lo and s < hi]
    host_stats: dict[str, list] = {}
    for n, s, e in host_spans:
        h = host_stats.setdefault(n, [0, 0.0])
        h[0] += 1
        h[1] += e - s

    gaps = []
    for s, e in gaps_of(union(merged_all, lo, hi), lo, hi):
        mid = (s + e) / 2
        covering = [(hs, n) for n, hs, he in host_spans if hs <= mid < he]
        name = max(covering)[1] if covering else "no bench span"
        gaps.append([name, e - s])
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(([n, s] for n, s in ops.items()), key=lambda o: -o[1])
    n_dev = max(1, len(dev_planes))
    return Summary(window_s=hi - lo, busy_s=busy_total / n_dev, devices=len(dev_planes),
                   modules=modules, ops=ops_sorted[:top], gaps=gaps[:top], host=host_stats)
