"""Walkthrough of the composable transport pipeline (the wire layer).

Every deposit a federated node makes flows through a ``TransportPipeline``
built from one spec string — ``"delta(chain=4)|npz"``, ``"topk(adaptive)"``,
``"quantized|zstd"`` — and every wire counter (bytes written/read, chain
depths, residual norms, prefetch activity) lands on that pipeline's stats.

This script pushes the same sparse-local-step schedule through several
pipelines over one shared schedule and prints what each one moved, then
demonstrates the two runtime features: background prefetch and
strategy-state recovery.

    PYTHONPATH=src python examples/transport_pipelines.py
"""
import numpy as np

from repro.core import (
    AsyncFederatedNode,
    InMemoryFolder,
    NodeUpdate,
    WeightStore,
    normalize_transport,
)
from repro.core.strategies import FedAvgM


def sparse_steps(n_params=200_000, pushes=12, fraction=0.005, seed=0):
    """A partial-fine-tuning-style schedule: each step perturbs a small
    fraction of entries — the regime delta transports are built for."""
    rng = np.random.default_rng(seed)
    cur = (np.arange(n_params, dtype=np.float32) % 997) * np.float32(1e-3)
    for _ in range(pushes):
        cur = cur.copy()
        idx = rng.integers(0, n_params, size=int(fraction * n_params))
        cur[idx] += rng.normal(size=idx.size).astype(np.float32)
        yield {"w": cur}


def compare_pipelines():
    specs = ["full", "quantized", "delta", "delta(chain=4)|zstd",
             "topk(adaptive)"]
    print("pipeline comparison\n")
    print(f"{'spec':<22}{'wire MB':>9}{'rebases':>9}{'re-anchors':>11}"
          f"{'max depth':>11}")
    for spec in specs:
        folder = InMemoryFolder()
        writer = WeightStore(folder, transport=spec)
        reader = WeightStore(folder)
        for ctr, params in enumerate(sparse_steps()):
            writer.push(NodeUpdate(params, num_examples=1, node_id="n",
                                   counter=ctr))
            reader.pull_node("n")
        s = writer.transport_stats()
        wire = (s["bytes_written"] + reader.bytes_read) / 1e6
        print(f"{writer.transport:<22}{wire:>9.2f}{s['rebases']:>9}"
              f"{s['reanchors']:>11}{s['max_chain_depth']:>11}")
    print("\nlegacy names map onto the same grammar:",
          f"delta_q -> {normalize_transport('delta_q')},",
          f"topk|delta -> {normalize_transport('topk|delta')}")


def prefetch_demo():
    print("\nbackground prefetch")
    folder = InMemoryFolder()
    writer = WeightStore(folder)
    reader = WeightStore(folder)
    for i in range(5):
        writer.push(NodeUpdate({"w": np.full((4096,), float(i), np.float32)},
                               num_examples=1, node_id=f"peer{i}", counter=0))
    reader.warm_cache()          # what the prefetch thread runs periodically
    reader.pull()                # the federation step itself: all cache hits
    s = reader.transport_stats()
    print(f"  warmed {s['prefetched']} peers ahead of time; "
          f"the pull paid {s['decode_hits']} cache hits, "
          f"{s['decode_misses'] - s['prefetched']} fresh decodes")


def recovery_demo():
    print("\nstrategy-state recovery (FedAvgM momentum survives a restart)")
    folder = InMemoryFolder()
    a = AsyncFederatedNode(strategy=FedAvgM(), shared_folder=folder,
                           node_id="a", persist_strategy_state=True)
    b = AsyncFederatedNode(strategy=FedAvgM(), shared_folder=folder,
                           node_id="b", persist_strategy_state=True)
    rng = np.random.default_rng(0)
    pa = {"w": rng.normal(size=(512,)).astype(np.float32)}
    pb = {"w": rng.normal(size=(512,)).astype(np.float32)}
    a.update_parameters(pa, num_examples=1)
    b.update_parameters(pb, num_examples=1)
    a.update_parameters(pa, num_examples=1)         # aggregates + persists
    momentum = float(np.abs(a.strategy.buf).sum())
    a2 = AsyncFederatedNode(strategy=FedAvgM(), shared_folder=folder,
                            node_id="a", persist_strategy_state=True)
    restored = float(np.abs(a2.strategy.buf).sum()) if a2.strategy.buf is not None else 0.0
    print(f"  |momentum| before crash = {momentum:.4f}, "
          f"after restart = {restored:.4f} "
          f"({'restored' if restored == momentum else 'LOST'})")


if __name__ == "__main__":
    compare_pipelines()
    prefetch_demo()
    recovery_demo()
