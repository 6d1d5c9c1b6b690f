"""Serving tier: bulk prefill equivalence, hot-swap atomicity, restart
resume, the ``repro.api`` facade grammar, and the SERVE observability row."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import connect, serve
from repro.configs import get_config
from repro.core.gossip import ShardedWeightStore
from repro.core.serialize import NodeUpdate
from repro.core.store import CachingFolder, DiskFolder, InMemoryFolder, RetryFolder, WeightStore, make_folder
from repro.core.telemetry import collect_obs
from repro.models import build_model
from repro.obs import render_dashboard
from repro.serving import ServingNode, StoreWatcher


def _push(store, params, *, counter, node_id="trainer-0"):
    store.push(NodeUpdate(params=params, num_examples=1, node_id=node_id,
                          counter=counter, timestamp=time.time()))


# ---------------------------------------------------------------------------
# bulk prefill == token-at-a-time loop (every decode-path block family)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [
    "pythia-14m",          # GQA attention
    "mamba2-130m",         # SSM (conv window + chunked scan state)
    "recurrentgemma-9b",   # RG-LRU + windowed attention hybrid
    "minicpm3-4b",         # MLA latent attention
    "gemma-7b",            # sliding window + logit softcap
    "seamless-m4t-medium", # enc-dec (self + cross attention)
])
def test_bulk_prefill_matches_decode_loop(arch):
    from repro.launch.serve import serve_batch, serve_batch_loop
    from repro.models.frontends import stub_audio_frames

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    prompts = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size, jnp.int32)
    kwargs = {}
    if cfg.is_encdec:
        kwargs["frames"] = stub_audio_frames(rng, cfg, 2, 16)
    fast = serve_batch(cfg, params, prompts, new_tokens=6, **kwargs)
    slow = serve_batch_loop(cfg, params, prompts, new_tokens=6, **kwargs)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


# ---------------------------------------------------------------------------
# ServingNode: deploy, hot swap, atomicity, restart resume
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_cfg():
    return get_config("pythia-14m").reduced()


def test_hot_swap_no_torn_read(smoke_cfg):
    """A swap landing mid-batch must not affect that batch (snapshot
    semantics), and the NEXT batch must run on the new weights."""
    model = build_model(smoke_cfg)
    params_a = model.init(jax.random.PRNGKey(0))
    params_b = jax.tree.map(lambda x: -x, params_a)

    store = WeightStore(InMemoryFolder())
    _push(store, params_a, counter=0)
    node = ServingNode(store, smoke_cfg)  # no watcher thread: manual polls
    assert node.poll_once()
    prompts = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                           smoke_cfg.vocab_size, jnp.int32))

    expected_a, _ = node.generate(prompts, new_tokens=6)

    # deploy B mid-batch via the on_token hook (same thread -> the swap
    # really does complete between decode steps of the in-flight batch)
    def swap_mid_batch(t):
        if t == 2:
            _push(store, params_b, counter=1)
            assert node.poll_once()

    mid, meta = node.generate(prompts, new_tokens=6, on_token=swap_mid_batch)
    assert node.stats()["swaps"] == 2
    assert meta["counter"] == 0  # the batch kept its snapshot
    np.testing.assert_array_equal(mid, expected_a)

    after, meta = node.generate(prompts, new_tokens=6)
    assert meta["counter"] == 1
    from repro.launch.serve import serve_batch

    expected_b = np.asarray(serve_batch(smoke_cfg, params_b, jnp.asarray(prompts),
                                        new_tokens=6))
    np.testing.assert_array_equal(after, expected_b)
    assert not np.array_equal(expected_a, expected_b)


def test_restart_resumes_from_latest(smoke_cfg):
    model = build_model(smoke_cfg)
    params = model.init(jax.random.PRNGKey(0))
    folder = InMemoryFolder()
    _push(WeightStore(folder), params, counter=7)

    node1 = ServingNode(WeightStore(folder), smoke_cfg)
    assert node1.poll_once()
    assert node1.stats()["counter"] == 7

    # a fresh node against the same folder deploys from latest/ with no new
    # pushes — serving restarts are stateless
    node2 = ServingNode(WeightStore(folder), smoke_cfg)
    assert node2.poll_once()
    assert node2.stats()["counter"] == 7
    assert node2.stats()["deployed"]


def test_incompatible_updates_skipped(smoke_cfg):
    other_cfg = get_config("mamba2-130m").reduced()
    other_params = build_model(other_cfg).init(jax.random.PRNGKey(0))
    store = WeightStore(InMemoryFolder())
    _push(store, other_params, counter=3)

    node = ServingNode(store, smoke_cfg)
    assert not node.poll_once()
    assert not node.stats()["deployed"]
    assert node.watcher.skipped_incompatible >= 1
    # incompatible counters still drive the staleness reference
    assert node.watcher.last_max_counter == 3
    with pytest.raises(RuntimeError, match="no weights deployed"):
        node.generate(np.zeros((1, 4), np.int32), new_tokens=2)


def test_watcher_picks_freshest_and_dedups(smoke_cfg):
    model = build_model(smoke_cfg)
    params = model.init(jax.random.PRNGKey(0))
    store = WeightStore(InMemoryFolder())
    _push(store, params, counter=0, node_id="a")
    _push(store, params, counter=5, node_id="b")

    watcher = StoreWatcher(store, spec=ServingNode(store, smoke_cfg).spec)
    dep = watcher.poll()
    assert dep is not None and dep.source == "b" and dep.counter == 5
    assert watcher.poll() is None  # unchanged store -> no redeploy
    _push(store, params, counter=6, node_id="a")
    dep = watcher.poll()
    assert dep is not None and dep.source == "a" and dep.counter == 6


def test_failed_deploy_is_counted(smoke_cfg, monkeypatch):
    """A deploy that raises (say, the device runs out of memory while the
    standby leaves are written) keeps the watcher alive but is counted, so a
    caller can fail on it instead of serving older weights unawares."""
    params = build_model(smoke_cfg).init(jax.random.PRNGKey(0))
    store = WeightStore(InMemoryFolder())
    _push(store, params, counter=0)
    node = ServingNode(store, smoke_cfg)

    def out_of_memory(idx, buf):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected")

    with monkeypatch.context() as m:
        m.setattr(node, "_materialize_chunked", out_of_memory)
        assert not node.poll_once()
    assert node.stats()["deploy_failures"] == 1
    assert not node.stats()["deployed"]

    _push(store, params, counter=1)
    assert node.poll_once()
    stats = node.stats()
    assert stats["deployed"] and stats["counter"] == 1
    assert stats["deploy_failures"] == 1


def test_serving_node_rejects_encdec():
    with pytest.raises(ValueError, match="decoder-only"):
        ServingNode(WeightStore(InMemoryFolder()), "seamless-m4t-medium",
                    reduced=True)


def test_stats_keys(smoke_cfg):
    node = ServingNode(WeightStore(InMemoryFolder()), smoke_cfg)
    stats = node.stats()
    for key in ("deployed", "source", "counter", "swaps", "deploy_failures", "requests", "tokens",
                "tokens_per_sec", "swap_ms_p50", "swap_ms_p99", "swap_ms_max",
                "staleness_mean", "staleness_max", "skipped_incompatible"):
        assert key in stats
    assert not stats["deployed"] and stats["counter"] == -1


# ---------------------------------------------------------------------------
# the repro.api facade
# ---------------------------------------------------------------------------


def test_connect_uri_stage_combinations(tmp_path):
    cases = {
        "memory://t-plain": InMemoryFolder,
        "cache+memory://t-cache": CachingFolder,
        "retry+memory://t-retry": RetryFolder,
        "cache+retry+memory://t-cr": CachingFolder,
        "retry+cache+memory://t-rc": RetryFolder,
        str(tmp_path / "disk"): DiskFolder,
    }
    params = {"w": np.arange(4, dtype=np.float32)}
    for uri, folder_kind in cases.items():
        writer = connect(uri)
        assert isinstance(writer, WeightStore)
        assert isinstance(writer.folder, folder_kind), uri
        _push(writer, params, counter=1)
        # a SECOND connect to the same URI sees the deposit (named memory://
        # shares one process-global folder; disk shares the directory)
        reader = connect(uri)
        updates = reader.pull()
        assert len(updates) == 1 and updates[0].counter == 1, uri
        np.testing.assert_array_equal(updates[0].params["w"], params["w"])


def test_connect_sharded_uris():
    for uri in ("shard2+memory://t-sh2", "shard4x2+memory://t-sh42",
                "shard2+cache+memory://t-shc"):
        store = connect(uri)
        assert isinstance(store, ShardedWeightStore)
    # named memory shares per-group folders across connects: a fleet-wide
    # scan on a SECOND connect sees the first connect's deposit
    a = connect("shard2+memory://t-shared")
    b = connect("shard2+memory://t-shared")
    a.push(NodeUpdate(params={"w": np.ones(3, np.float32)}, num_examples=1,
                      node_id="n0", counter=0, timestamp=time.time()))
    assert any(u.node_id == "n0" for u in b.pull())


def test_connect_validates_and_normalizes():
    with pytest.raises(ValueError):
        connect("shard2+shard2+memory://bad")  # shard must be outermost
    with pytest.raises(ValueError):
        connect("cache+shard2+memory://bad")
    with pytest.raises(ValueError, match="not both"):
        connect("memory://", transport="delta", families=("adapters",))
    with pytest.raises(ValueError):
        connect("memory://", transport="no-such-codec")
    # legacy names and flags still work, mapped to canonical pipeline specs
    for kwargs in ({"transport": "delta_q"}, {"transport": "full"},
                   {"quantized": True}):
        store = connect("memory://", **kwargs)
        _push(store, {"w": np.ones(8, np.float32)}, counter=0)
        assert len(store.pull()) == 1
    # quantized maps uniformly for sharded stores too (no ctor kwarg there)
    assert isinstance(connect("shard2+memory://", quantized=True),
                      ShardedWeightStore)


def test_connect_prefetch_contract():
    store = connect("memory://t-prefetch", prefetch=0.05)
    try:
        assert store._prefetcher is not None
    finally:
        store.stop_prefetch()
    with pytest.raises(ValueError, match="prefetch"):
        connect("shard2+memory://t-pf", prefetch=True)
    sharded = connect("shard2+memory://t-pf2", prefetch=(0.05, "n0"))
    sharded.stop_prefetch()


def test_fleet_spec_connect_uses_facade():
    from repro.core.fleet import FleetSpec

    spec = FleetSpec(store_uri="memory://t-fleet", transport="delta")
    store = spec.connect()
    assert isinstance(store, WeightStore)
    # the spec's transport is the default; an override wins
    assert isinstance(spec.connect(transport="full"), WeightStore)


def test_api_serve_facade(smoke_cfg):
    params = build_model(smoke_cfg).init(jax.random.PRNGKey(0))
    _push(connect("memory://t-serve-facade"), params, counter=0)
    node = serve("memory://t-serve-facade", smoke_cfg, poll_interval=0.02,
                 wait=30.0)
    try:
        assert node.stats()["deployed"]
        out, meta = node.generate(np.zeros((1, 4), np.int32), new_tokens=3)
        assert out.shape == (1, 3) and meta["counter"] == 0
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# SERVE observability row
# ---------------------------------------------------------------------------


def test_serve_row_in_dashboard(smoke_cfg):
    params = build_model(smoke_cfg).init(jax.random.PRNGKey(0))
    uri = "memory://t-serve-obs"
    _push(connect(uri), params, counter=0)

    store = connect(uri)
    node = ServingNode(store, smoke_cfg, telemetry=True, node_id="server-0")
    assert node.poll_once()
    node.generate(np.zeros((2, 4), np.int32), new_tokens=3)
    node.flush_obs()

    rollups = render_dashboard(collect_obs(uri), printer=lambda *_: None)
    assert rollups["nodes"]["server-0"]["role"] == "serve"
    assert rollups["nodes"]["server-0"]["serve"]["swaps"] == 1
    assert rollups["fleet"]["serving_nodes"] == 1

    lines = []
    render_dashboard(collect_obs(uri), printer=lines.append)
    assert any("SERVE" in line for line in lines)
    assert any("server-0" in line for line in lines)
