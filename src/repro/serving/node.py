"""ServingNode: hot-swapping batched decode over a federation store.

Double buffering, on both sides of the host/device boundary. The watcher
thread decodes a fresh deployment into the *standby* host f32 buffer
(``np.copyto`` for flat-path updates, ``LeafSpec.flatten_into`` for
tree-path ones), materializes the standby *device* leaf set, and publishes
it with one atomic reference flip. A decode batch snapshots the active tree
once at batch start, so:

  * a swap landing mid-batch never changes the weights a batch started with
    (no torn read — the batch finishes on its snapshot; per-buffer in-flight
    counts keep a buffer's device leaves untouched until the last batch
    referencing them completes);
  * requests never wait on a swap (zero downtime — the flip is a reference
    assignment, all decode/materialize work happens off the request path).

Device materialization is *chunk-throttled*: the standby device leaves are
updated in place through a donated ``dynamic_update_slice`` in ~2 MB slices
with a yield between slices. One leaf-sized host→device copy would serialize
with decode executions on the device stream and stall in-flight requests for
hundreds of ms at 10^8 params; many small ops interleave, which is what
keeps p99 decode latency during a swap within the SLO (measured in
``benchmarks.run --only serve``).

Telemetry spans ``serve.prefill`` / ``serve.decode`` / ``serve.swap`` plus a
``serve`` SLO dict (swap-latency percentiles, staleness-in-rounds, token
throughput) ride ``obs/`` blobs like every trainer's metrics do.
"""
from __future__ import annotations

import logging
import threading
import time
import uuid
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.telemetry import Telemetry
from repro.core.tree import LeafSpec
from repro.launch.steps import make_bulk_prefill_step, make_serve_step
from repro.models import ModelConfig, build_model

from .watcher import Deployment, StoreWatcher

_log = logging.getLogger("repro.serving")

_SLO_WINDOW = 512  # swap/staleness samples kept for percentile SLOs

_SWAP_CHUNK = 512 * 1024  # f32 elements per donated device write (~2 MB)
_SWAP_PAUSE_S = 0.001     # yield between chunks so queued decodes interleave
_SWAP_DRAIN_TIMEOUT_S = 30.0  # max wait for a batch still on the standby leaves


@partial(jax.jit, donate_argnums=(0,))
def _chunk_write(leaf, chunk, start):
    """Donated in-place write of an f32 chunk at flat offset ``start``.

    Donation reuses ``leaf``'s device buffer, so a swap never allocates or
    copies a whole leaf at once — the reshape round-trip is a bitcast.
    """
    flat = leaf.reshape((-1,))
    flat = jax.lax.dynamic_update_slice_in_dim(
        flat, chunk.astype(leaf.dtype), start, axis=0
    )
    return flat.reshape(leaf.shape)


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


@dataclass
class _Deployed:
    """One published weight set; immutable once assigned to ``_deployed``."""

    params: Any        # device param tree (views of one host buffer's copy)
    source: str
    counter: int
    deployed_at: float
    buf: int | None = None  # device-buffer index (None: mesh/fallback tree)


class ServingNode:
    """Read-only federation member that serves the freshest store weights.

    Parameters
    ----------
    store:
        Any weight store (flat, sharded, hierarchical). The node only reads
        weights; its sole writes are its own ``obs/`` telemetry blobs.
    arch:
        Arch name from ``repro.configs`` or a full :class:`ModelConfig`.
    reduced:
        Shrink the config (``ModelConfig.reduced()``) — CI/smoke scale.
    poll_interval:
        Seconds between store freshness sweeps on the watcher thread.
    telemetry:
        ``Telemetry`` instance, bool, or None (``REPRO_OBS`` env default) —
        same contract as the trainer nodes.
    mesh:
        Optional ``jax.sharding.Mesh``: deployments are placed with
        ``launch.sharding.param_shardings`` instead of single-device.
    window_override:
        Optional sliding-window override threaded to prefill/decode.
    """

    def __init__(
        self,
        store,
        arch: str | ModelConfig,
        *,
        node_id: str | None = None,
        reduced: bool = False,
        poll_interval: float = 0.25,
        telemetry: "Telemetry | bool | None" = None,
        mesh=None,
        window_override: int | None = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
        if reduced:
            cfg = cfg.reduced()
        if cfg.is_encdec:
            raise ValueError(
                "ServingNode covers decoder-only archs (the federated zoo); "
                "use repro.launch.serve.serve_batch for enc-dec one-shots"
            )
        self.cfg = cfg
        self.model = build_model(cfg)
        self.store = store
        self.node_id = node_id or f"serve-{uuid.uuid4().hex[:8]}"
        self.poll_interval = float(poll_interval)
        self._clock = clock
        if isinstance(telemetry, Telemetry):
            self.telemetry = telemetry
        else:
            self.telemetry = Telemetry(self.node_id, enabled=telemetry)
        if self.telemetry.enabled and hasattr(store, "attach_telemetry"):
            store.attach_telemetry(self.telemetry)

        shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.spec = LeafSpec.of(shapes)
        self.watcher = StoreWatcher(store, spec=self.spec)
        self._shardings = None
        if mesh is not None:
            from repro.launch.sharding import param_shardings

            self._shardings = param_shardings(shapes, mesh)

        self._prefill = jax.jit(make_bulk_prefill_step(cfg, window_override=window_override))
        self._serve_step = jax.jit(make_serve_step(cfg, window_override=window_override))
        self._window_override = window_override

        # double buffer: standby is written + materialized off the request
        # path, then published by flipping one reference
        self._buffers = [self.spec.empty_flat(), self.spec.empty_flat()]
        self._standby = 0
        self._deployed: _Deployed | None = None
        self._deployed_event = threading.Event()
        # device-side double buffer for the chunk-throttled swap: two leaf
        # lists updated in place via donation. In-place writes would tear a
        # batch still decoding on the standby leaves (two swaps back), so a
        # per-buffer in-flight count gates the overwrite.
        self._dev_leaves: list[list | None] = [None, None]
        self._buf_refs = [0, 0]
        self._buf_cv = threading.Condition()

        self._lock = threading.Lock()
        self._swaps = 0
        self._deploy_failures = 0
        self._requests = 0
        self._tokens = 0
        self._serve_seconds = 0.0
        self._swap_ms: list[float] = []
        self._swap_log: list[tuple[float, float]] = []  # (t0, t1) monotonic
        self._stale_recent: list[float] = []

        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "ServingNode":
        """Start the watcher thread (idempotent)."""
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name=f"serving-{self.node_id}", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        self.flush_obs()

    def __enter__(self) -> "ServingNode":
        return self.start()

    def __exit__(self, *exc: object) -> None:
        self.stop()

    def wait_until_deployed(self, timeout: float | None = None) -> bool:
        """Block until the first weight set is live (True) or timeout."""
        return self._deployed_event.wait(timeout)

    # -- watcher thread -------------------------------------------------

    def _run(self) -> None:
        self.poll_once()  # immediate first sweep: resume-from-latest on start
        while not self._stop.wait(self.poll_interval):
            self.poll_once()

    def poll_once(self) -> bool:
        """One freshness sweep (also callable inline, e.g. from tests).
        Returns True when a new deployment was swapped in."""
        swapped = False
        dep = None
        try:
            with self.telemetry.span("serve.poll"):
                dep = self.watcher.poll()
        except Exception:
            _log.warning("serving node %s: poll failed", self.node_id, exc_info=True)
        if dep is not None:
            # the watcher thread must outlive a failed deploy, but serving
            # then stays on older weights: counted in stats() so that callers
            # can fail on it instead of reading a clean run
            try:
                self.deploy(dep)
                swapped = True
            except Exception:
                with self._lock:
                    self._deploy_failures += 1
                _log.error("serving node %s: deploy of %s@%d failed", self.node_id,
                           dep.source, dep.counter, exc_info=True)
        d = self._deployed
        if d is not None and self.watcher.last_max_counter is not None:
            behind = max(0, self.watcher.last_max_counter - d.counter)
            self.telemetry.observe_staleness(behind)
            with self._lock:
                self._stale_recent.append(float(behind))
                del self._stale_recent[:-_SLO_WINDOW]
        if self.telemetry.enabled:
            self.telemetry.end_round(aggregated=swapped)
            if self.telemetry.should_flush():
                self.flush_obs()
        return swapped

    def deploy(self, dep: Deployment) -> None:
        """Decode ``dep`` into the standby buffer, materialize the device
        tree, and publish it. Runs off the request path; ``generate`` never
        blocks on this."""
        t0 = self._clock()
        with self.telemetry.span("serve.swap"):
            idx = self._standby
            buf = self._buffers[idx]
            if dep.flat is not None:
                np.copyto(buf, dep.flat)
            else:
                self.spec.flatten_into(dep.params, buf)
            if self._shardings is not None:
                # mesh path: jnp.array (copy=True) so leaves own their memory
                # before device_put scatters them across the mesh
                tree = jax.tree.map(jnp.array, self.spec.unflatten(buf))
                tree = jax.device_put(tree, self._shardings)
                jax.block_until_ready(tree)
                buf_index = None
            else:
                tree = self._materialize_chunked(idx, buf)
                buf_index = idx
            # publish: one atomic reference flip; in-flight batches keep
            # their snapshot of the previous tree
            self._deployed = _Deployed(
                params=tree,
                source=dep.source,
                counter=dep.counter,
                deployed_at=self._clock(),
                buf=buf_index,
            )
            self._standby ^= 1
        t1 = self._clock()
        with self._lock:
            self._swaps += 1
            self._swap_ms.append((t1 - t0) * 1e3)
            del self._swap_ms[:-_SLO_WINDOW]
            self._swap_log.append((t0, t1))
            del self._swap_log[:-_SLO_WINDOW]
        self.telemetry.count("serve.swaps")
        self._deployed_event.set()

    def _materialize_chunked(self, idx: int, buf: np.ndarray) -> Any:
        """Write the standby host buffer into device leaf set ``idx`` in
        ~2 MB donated chunks, yielding between chunks so decode steps queued
        on the device stream interleave instead of stalling behind one
        leaf-sized copy."""
        # the in-place writes would tear a batch still decoding on this
        # buffer's previous leaves — wait for it to drain (the OTHER buffer
        # stays live the whole time; new batches snapshot that one)
        with self._buf_cv:
            drained = self._buf_cv.wait_for(
                lambda: self._buf_refs[idx] == 0, timeout=_SWAP_DRAIN_TIMEOUT_S
            )
        leaves = self._dev_leaves[idx] if drained else None
        if leaves is None:
            # first swap into this buffer — or a wedged batch at timeout, in
            # which case fresh allocations keep the old leaves intact
            leaves = [
                jnp.zeros(s, d)
                for s, d in zip(self.spec.shapes, self.spec.dtypes)
            ]
        out = []
        last = len(leaves) - 1
        for i, leaf in enumerate(leaves):
            o = int(self.spec.offsets[i])
            n = int(self.spec.sizes[i])
            pos = 0
            while pos < n:
                m = min(_SWAP_CHUNK, n - pos)
                chunk = jnp.asarray(buf[o + pos : o + pos + m])
                leaf = _chunk_write(leaf, chunk, jnp.int32(pos))
                leaf.block_until_ready()
                pos += m
                if pos < n or i < last:
                    time.sleep(_SWAP_PAUSE_S)
            out.append(leaf)
        self._dev_leaves[idx] = out
        return jax.tree_util.tree_unflatten(self.spec.treedef, out)

    # -- request path ---------------------------------------------------

    def generate(
        self,
        prompts,
        *,
        new_tokens: int,
        on_token: Callable[[int], None] | None = None,
    ) -> tuple[np.ndarray, dict]:
        """Batched greedy decode on the currently deployed weights.

        prompts: (B, S) int32 → ((B, new_tokens) continuations, meta).
        The active weight set is snapshotted once at batch start — a swap
        landing mid-batch does not affect this batch. ``on_token`` (if set)
        is called after each generated token with its index; meta carries
        per-token decode spans on the node's monotonic clock for SLO math.
        """
        # snapshot + in-flight increment under one lock: once the watcher
        # sees a zero refcount for the standby buffer, no new batch can
        # start on it (any new snapshot points at the active buffer)
        with self._buf_cv:
            dep = self._deployed
            if dep is not None and dep.buf is not None:
                self._buf_refs[dep.buf] += 1
        if dep is None:
            raise RuntimeError(
                f"serving node {self.node_id}: no weights deployed yet "
                "(wait_until_deployed, or check the store has pushed updates)"
            )
        try:
            return self._generate_on(dep, prompts, new_tokens, on_token)
        finally:
            if dep.buf is not None:
                with self._buf_cv:
                    self._buf_refs[dep.buf] -= 1
                    self._buf_cv.notify_all()

    def _generate_on(self, dep, prompts, new_tokens, on_token):
        prompts = jnp.asarray(prompts, jnp.int32)
        B, S = prompts.shape
        cache = self.model.init_cache(
            B, capacity=S + new_tokens, window_override=self._window_override
        )
        t_start = self._clock()
        with self.telemetry.span("serve.prefill"):
            tok, cache = self._prefill(dep.params, prompts, cache)
            tok.block_until_ready()
        t_prefill = self._clock()
        toks = [tok]
        decode_spans: list[tuple[float, float]] = []
        if on_token is not None:
            on_token(0)
        for t in range(1, new_tokens):
            ts = self._clock()
            with self.telemetry.span("serve.decode"):
                tok, cache = self._serve_step(dep.params, tok, cache, jnp.int32(S - 1 + t))
                tok.block_until_ready()
            decode_spans.append((ts, self._clock()))
            toks.append(tok)
            if on_token is not None:
                on_token(t)
        t_end = self._clock()
        n_tokens = B * new_tokens
        with self._lock:
            self._requests += 1
            self._tokens += n_tokens
            self._serve_seconds += t_end - t_start
        self.telemetry.count("serve.requests")
        self.telemetry.count("serve.tokens", n_tokens)
        meta = {
            "source": dep.source,
            "counter": dep.counter,
            "prefill_s": t_prefill - t_start,
            "decode_spans": decode_spans,
            "batch_span": (t_start, t_end),
        }
        return np.asarray(jnp.stack(toks, axis=1)), meta

    # -- SLOs / observability -------------------------------------------

    def swap_log(self) -> list[tuple[float, float]]:
        """Recent (start, end) swap intervals on the node's monotonic clock."""
        with self._lock:
            return list(self._swap_log)

    def stats(self) -> dict:
        """Serving SLO rollup — also the ``serve`` dict in obs payloads."""
        with self._lock:
            swap_sorted = sorted(self._swap_ms)
            stale = list(self._stale_recent)
            swaps, requests, tokens = self._swaps, self._requests, self._tokens
            deploy_failures = self._deploy_failures
            serve_seconds = self._serve_seconds
        d = self._deployed
        return {
            "deployed": d is not None,
            "source": d.source if d else "",
            "counter": d.counter if d else -1,
            "swaps": swaps,
            "deploy_failures": deploy_failures,
            "requests": requests,
            "tokens": tokens,
            "tokens_per_sec": round(tokens / serve_seconds, 3) if serve_seconds > 0 else 0.0,
            "swap_ms_p50": round(_percentile(swap_sorted, 0.5), 3),
            "swap_ms_p99": round(_percentile(swap_sorted, 0.99), 3),
            "swap_ms_max": round(swap_sorted[-1], 3) if swap_sorted else 0.0,
            "staleness_mean": round(sum(stale) / len(stale), 4) if stale else 0.0,
            "staleness_max": max(stale) if stale else 0.0,
            "skipped_incompatible": self.watcher.skipped_incompatible,
        }

    def flush_obs(self) -> None:
        """Deposit one ``obs/<node>/<seq>`` blob with the serve SLO dict."""
        if not self.telemetry.enabled:
            return
        try:
            transport = self.store.transport_stats() if hasattr(self.store, "transport_stats") else None
            payload = self.telemetry.snapshot(transport)
            payload["serve"] = self.stats()
            self.store.push_obs(
                self.node_id, payload["seq"], payload, keep=self.telemetry.obs_keep
            )
        except Exception:
            # observability must never take down serving
            _log.debug("serving node %s: obs flush failed", self.node_id, exc_info=True)
