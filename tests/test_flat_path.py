"""Flat-vector hot path ≡ per-leaf tree path.

The PR-3 contract: every strategy executed vectorized over stacked flats
matches the PR-2 per-leaf reference (``strategies_ref``) within 1e-6 over
multi-round *stateful* sequences (momentum/moment buffers, FedBuff buffering,
FedAsync staleness), the store's flat decode reproduces the tree decode
bitwise for every transport (full/quantized/delta/delta_q/topk), and
flat↔tree round-trips preserve mixed-dtype pytrees exactly.
"""
import io
import json
import zipfile

import numpy as np
import pytest
from _hyp import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import (
    CachingFolder,
    DiskFolder,
    FlatUpdate,
    InMemoryFolder,
    LeafSpec,
    NodeUpdate,
    WeightStore,
)
from repro.core.serialize import (
    NpzEnvelope,
    content_hash,
    decode_params_flat,
    deserialize_params,
    deserialize_update,
    deserialize_update_delta,
    deserialize_update_quantized,
    maybe_decompress,
    peek_meta,
    serialize_update,
    serialize_update_quantized,
)
from repro.core.strategies import STRATEGIES, FedAvg, FedAvgM, get_strategy
from repro.core.strategies_ref import REF_STRATEGIES, get_ref_strategy
from repro.core.tree import path_str


def tree_of(vals, shift=0.0):
    """A small multi-leaf nested model, deterministic in (vals, shift)."""
    rng = np.random.default_rng(int(abs(vals[0]) * 1000) % 2**31)
    return {
        "enc": {
            "w": (np.linspace(-1, 1, 12, dtype=np.float32).reshape(4, 3)
                  * np.float32(vals[0]) + np.float32(shift)),
            "b": np.full((3,), np.float32(vals[1] % 3.0)),
        },
        "head": (rng.normal(size=(5,)).astype(np.float32) * np.float32(0.1)
                 + np.float32(vals[1])),
    }


def pair(vals, *, n=10, node="x", counter=0, spec=None):
    """(tree NodeUpdate, FlatUpdate) with identical content — the tree one
    feeds the reference path, the flat one the vectorized path."""
    params = tree_of(vals)
    tree_u = NodeUpdate(params, num_examples=n, node_id=node, counter=counter)
    spec = spec or LeafSpec.of(params)
    flat_u = FlatUpdate(spec.flatten(params), spec,
                        num_examples=n, node_id=node, counter=counter)
    return tree_u, flat_u, spec


STRATEGY_KWARGS = {
    "fedavg": {},
    "fedavgm": dict(server_lr=0.7, momentum=0.85),
    "fedadam": dict(server_lr=0.3, tau=0.05),
    "fedyogi": dict(server_lr=0.3, tau=0.05),
    "fedadagrad": dict(server_lr=0.3, tau=0.05),
    "fedasync": dict(alpha=0.55, staleness_fn="poly", a=0.6),
    "fedbuff": dict(buffer_size=2),
    "partial_fedavg": dict(shared_pattern=r"^enc/"),
}


@settings(max_examples=8, deadline=None)
@given(
    rounds=st.lists(st.lists(st.floats(-2, 2), min_size=2, max_size=8),
                    min_size=3, max_size=5),
    ns=st.lists(st.integers(1, 50), min_size=8, max_size=8),
    lags=st.lists(st.integers(0, 6), min_size=8, max_size=8),
)
def test_every_strategy_flat_matches_tree_over_stateful_rounds(rounds, ns, lags):
    """Multi-round equivalence: the SAME strategy instance carries its state
    (momentum buffers, FedBuff buffer, FedAsync staleness) across rounds on
    both paths; results must stay within 1e-6 at every round."""
    assert sorted(STRATEGIES) == sorted(REF_STRATEGIES)
    for name in sorted(STRATEGIES):
        flat_strat = get_strategy(name, **STRATEGY_KWARGS[name])
        ref_strat = get_ref_strategy(name, **STRATEGY_KWARGS[name])
        spec = None
        for r, vals in enumerate(rounds):
            own_vals, peer_vals = vals[:2], vals[2:]
            own_t, own_f, spec = pair(own_vals, n=ns[0], node="me",
                                      counter=r + 6, spec=spec)
            peers_t, peers_f = [], []
            for i in range(0, len(peer_vals), 2):
                pv = peer_vals[i:i + 2]
                if len(pv) < 2:
                    pv = [pv[0], 0.5]
                j = i // 2
                pt, pf, spec = pair(pv, n=ns[1 + j], node=f"p{j}",
                                    counter=max(0, r + 6 - lags[j]), spec=spec)
                peers_t.append(pt)
                peers_f.append(pf)
            out_ref = ref_strat.aggregate(own_t, peers_t)
            out_flat = flat_strat.aggregate(own_f, peers_f)
            for leaf_path in (("enc", "w"), ("enc", "b"), ("head",)):
                a, b = out_flat, out_ref
                for k in leaf_path:
                    a, b = a[k], b[k]
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                    err_msg=f"{name} diverged at round {r}, leaf {leaf_path}")


def test_flat_strategies_accept_plain_tree_updates():
    """No store in the loop: strategies build their own spec from tree-only
    NodeUpdates and still agree with the reference."""
    own = NodeUpdate(tree_of([1.0, 2.0]), num_examples=3, node_id="a")
    peer = NodeUpdate(tree_of([0.5, -1.0]), num_examples=9, node_id="b")
    out = FedAvg().aggregate(own, [peer])
    ref = get_ref_strategy("fedavg").aggregate(own, [peer])
    np.testing.assert_allclose(out["enc"]["w"], ref["enc"]["w"], rtol=1e-6, atol=1e-6)
    assert out["head"].dtype == np.float32


def test_use_kernel_is_plumbed_through_every_strategy(monkeypatch):
    """Satellite regression: FedAvgM/_FedOpt used to drop use_kernel on the
    floor. Now every strategy's combine routes through the kernel ops when
    asked — observed by counting aggregate_flat/fed_opt_flat calls."""
    from repro.kernels.fed_agg import ops as fed_ops

    calls = {"n": 0}
    real_agg, real_opt = fed_ops.aggregate_flat, fed_ops.fed_opt_flat

    def spy_agg(*a, **k):
        calls["n"] += 1
        return real_agg(*a, **k)

    def spy_opt(*a, **k):
        calls["n"] += 1
        return real_opt(*a, **k)

    monkeypatch.setattr(fed_ops, "aggregate_flat", spy_agg)
    monkeypatch.setattr(fed_ops, "fed_opt_flat", spy_opt)
    for name in sorted(STRATEGIES):
        kwargs = dict(STRATEGY_KWARGS[name], use_kernel=True)
        if name == "fedbuff":
            kwargs["buffer_size"] = 1
        strat = get_strategy(name, **kwargs)
        before = calls["n"]
        own, own_f, spec = pair([1.0, 0.5], node="me", counter=3)
        _, p0, spec = pair([0.2, -0.3], node="p0", counter=2, spec=spec)
        strat.aggregate(own_f, [p0])
        assert calls["n"] > before, f"{name} never reached the kernel ops"


def test_kernel_and_plain_flat_paths_agree():
    for name in sorted(STRATEGIES):
        plain = get_strategy(name, **STRATEGY_KWARGS[name])
        kern = get_strategy(name, **dict(STRATEGY_KWARGS[name], use_kernel=True))
        own_t, own_f, spec = pair([1.5, -0.5], node="me", counter=4)
        _, p0, spec = pair([0.3, 0.9], node="p0", counter=3, spec=spec)
        _, p1, spec = pair([-1.1, 0.1], node="p1", counter=1, spec=spec)
        a = plain.aggregate(own_f, [p0, p1])
        b = kern.aggregate(own_f, [p0, p1])
        np.testing.assert_allclose(a["enc"]["w"], b["enc"]["w"],
                                   rtol=1e-5, atol=1e-5, err_msg=name)


# --- flat ↔ tree round-trips -------------------------------------------------


def test_leafspec_roundtrip_mixed_dtypes():
    """bf16 / f16 / int32 / f32 leaves all survive flatten→unflatten exactly
    (ints small enough to embed in f32 — the store refuses the rest)."""
    tree = {
        "w32": np.linspace(-3, 3, 8, dtype=np.float32).reshape(2, 4),
        "h": {"w16": np.linspace(-1, 1, 6, dtype=np.float16),
              "steps": np.arange(5, dtype=np.int32)},
        "wb": jnp.asarray(np.linspace(-2, 2, 7), jnp.bfloat16),
    }
    spec = LeafSpec.of(tree)
    assert spec.num_params == 8 + 6 + 5 + 7
    out = spec.unflatten(spec.flatten(tree))
    assert out["w32"].dtype == np.float32 and out["w32"].shape == (2, 4)
    assert out["h"]["w16"].dtype == np.float16
    assert out["h"]["steps"].dtype == np.int32
    assert out["wb"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(out["w32"], tree["w32"])
    np.testing.assert_array_equal(out["h"]["w16"], tree["h"]["w16"])
    np.testing.assert_array_equal(out["h"]["steps"], np.asarray(tree["h"]["steps"]))
    np.testing.assert_array_equal(np.asarray(out["wb"], np.float32),
                                  np.asarray(tree["wb"], np.float32))
    # shared layout: a second tree of the same structure reuses the spec
    assert spec.describes(out) and spec.f32_exact is False  # int leaf


@settings(max_examples=15, deadline=None)
@given(vals=st.lists(st.floats(-4, 4), min_size=2, max_size=2))
def test_leafspec_flatten_matches_wire_decode(vals):
    """spec.flatten(tree) == the flat vector the store decodes from that
    tree's wire blob — the invariant the topk writer's error feedback rests
    on."""
    params = tree_of(vals)
    spec = LeafSpec.of(params)
    blob = serialize_update(NodeUpdate(params, num_examples=1, node_id="n"))
    wire_spec, flat, _meta = decode_params_flat(blob, {})
    assert wire_spec.key == spec.key
    np.testing.assert_array_equal(flat, spec.flatten(params))


def test_leafspec_shared_identity_across_store_pulls():
    """Stacked-flat pulls: every FlatUpdate a store returns for one model
    shares ONE spec instance, and unchanged peers' flats are the same array
    object across pulls (zero-copy steady state for the stack cache)."""
    store = WeightStore(InMemoryFolder())
    for i in range(3):
        store.push(NodeUpdate(tree_of([1.0 + i, -i * 0.5]), num_examples=1,
                              node_id=f"n{i}", counter=0))
    first = store.pull()
    assert len(first) == 3
    assert all(isinstance(u, FlatUpdate) for u in first)
    specs = {id(u.spec) for u in first}
    assert len(specs) == 1, "peers of one model must share a spec instance"
    again = store.pull()
    for a, b in zip(first, again):
        assert a.flat is b.flat  # decode-cache hit: identical array object


# --- transport equivalence: flat decode ≡ tree decode, bitwise ---------------


def _run_store(tmp_path, transport, rounds=6, **kw):
    folder = DiskFolder(str(tmp_path / transport))
    store = WeightStore(folder, transport=transport, rebase_every=3, **kw)
    rng = np.random.default_rng(7)
    params = tree_of([1.0, 0.5])
    history = []
    for ctr in range(rounds):
        # sparse local step: the regime delta/topk transports are for
        flat_view = np.concatenate([params["enc"]["w"].ravel(),
                                    params["enc"]["b"], params["head"]])
        idx = rng.choice(flat_view.size, size=3, replace=False)
        flat_view[idx] += rng.normal(size=3).astype(np.float32)
        w = flat_view[:12].reshape(4, 3).copy()
        params = {"enc": {"w": w, "b": flat_view[12:15].copy()},
                  "head": flat_view[15:].copy()}
        store.push(NodeUpdate(params, num_examples=1, node_id="n", counter=ctr))
        history.append(params)
    return folder, store, history


@pytest.mark.parametrize("transport", ["full", "quantized", "delta", "delta_q", "topk"])
def test_flat_decode_matches_tree_decode_bitwise(tmp_path, transport):
    """For every transport: a fresh reader's flat-path pull reconstructs the
    byte-identical params the per-leaf tree decode of the same blobs yields."""
    folder, _writer, _history = _run_store(tmp_path, transport)
    reader = WeightStore(folder)
    pulled = reader.pull_node("n")
    assert isinstance(pulled, FlatUpdate)
    # decode the very same blobs through the PR-2 per-leaf path
    blob = folder.get("latest/n")
    meta = peek_meta(blob)
    if meta.get("delta_of"):
        base_blob = folder.get(f"base/n/{meta['delta_of']}")
        assert content_hash(base_blob) == meta["delta_of"]
        ref = deserialize_update_delta(blob, deserialize_update(base_blob).params)
    elif meta.get("quantized"):
        ref = deserialize_update_quantized(blob)
    else:
        ref = deserialize_update(blob)
    for path in (("enc", "w"), ("enc", "b"), ("head",)):
        a, b = pulled.params, ref.params
        for k in path:
            a, b = a[k], b[k]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{transport} leaf {path}")
    assert (pulled.counter, pulled.num_examples) == (ref.counter, ref.num_examples)


def test_lossless_transports_reproduce_pushed_params_exactly(tmp_path):
    for transport in ("full", "delta"):
        folder, _store, history = _run_store(tmp_path, transport)
        pulled = WeightStore(folder).pull_node("n")
        np.testing.assert_array_equal(pulled.params["enc"]["w"], history[-1]["enc"]["w"])
        np.testing.assert_array_equal(pulled.params["head"], history[-1]["head"])


def test_int_params_fall_back_to_tree_decode_losslessly():
    """Leaves that don't embed in f32 must NOT go flat: a big int64 value
    survives the store bit-exactly via the tree fallback."""
    store = WeightStore(InMemoryFolder())
    big = np.asarray([2**40 + 3, 7], np.int64)
    store.push(NodeUpdate({"ids": big, "w": np.ones((2,), np.float32)},
                          num_examples=1, node_id="n", counter=0))
    pulled = store.pull_node("n")
    assert not isinstance(pulled, FlatUpdate)
    np.testing.assert_array_equal(pulled.params["ids"], big)
    # and strategies still aggregate such updates (via spec.flatten fallback)
    out = FedAvg().aggregate(pulled, [pulled])
    assert out["w"].shape == (2,)


# --- top-k / error feedback ---------------------------------------------------


def big_tree(fill) -> dict:
    """Large enough that npz container overhead never trips the writer's
    'delta must actually be smaller than a full deposit' rebase guard."""
    return {"w": np.full((64, 64), np.float32(fill)),
            "b": np.linspace(-1, 1, 512, dtype=np.float32) * np.float32(fill)}


def test_topk_error_feedback_drains_residual():
    """Pushing the SAME params repeatedly must converge the readers' view to
    those params exactly: each push ships the top-k of what is still unsent,
    so the residual drains to zero within ~1/fraction pushes."""
    store = WeightStore(InMemoryFolder(), transport="topk", topk_fraction=0.25,
                        rebase_every=100)
    store.push(NodeUpdate(big_tree(1.0), num_examples=1, node_id="n", counter=0))
    target = big_tree(-2.0)  # every entry differs from base
    for ctr in range(1, 7):  # ceil(1/0.25) + slack
        store.push(NodeUpdate(target, num_examples=1, node_id="n", counter=ctr))
    pulled = WeightStore(store.folder).pull_node("n")
    np.testing.assert_array_equal(pulled.params["w"], target["w"])
    np.testing.assert_array_equal(pulled.params["b"], target["b"])


def test_topk_ships_bounded_updates_and_reader_progresses():
    """Each non-rebase push ships ≤ k new entries; intermediate reader views
    move monotonically toward the target (lossy but convergent)."""
    N = 4096
    k = int(0.01 * N)
    store = WeightStore(InMemoryFolder(), transport="topk", topk_fraction=0.01,
                        rebase_every=100)
    store.push(NodeUpdate({"w": np.zeros((N,), np.float32)}, num_examples=1,
                          node_id="n", counter=0))
    target = {"w": np.linspace(1, 2, N).astype(np.float32)}
    errs = []
    reader = WeightStore(store.folder)
    for ctr in range(1, 5):
        store.push(NodeUpdate(target, num_examples=1, node_id="n", counter=ctr))
        pulled = reader.pull_node("n")
        errs.append(float(np.abs(pulled.params["w"] - target["w"]).sum()))
        changed = int(np.count_nonzero(pulled.params["w"]))
        assert 0 < changed <= k * ctr
    assert errs == sorted(errs, reverse=True)
    assert errs[0] > errs[-1]


def test_topk_blobs_are_smaller_than_full():
    store = WeightStore(InMemoryFolder(), transport="topk", topk_fraction=0.01,
                        rebase_every=100)
    store.push(NodeUpdate(big_tree(1.0), num_examples=1, node_id="n", counter=0))
    store.push(NodeUpdate(big_tree(1.5), num_examples=1, node_id="n", counter=1))
    blob = store.folder.get("latest/n")
    assert peek_meta(blob)["delta_of"]
    full = store.folder.get(f"base/n/{peek_meta(blob)['delta_of']}")
    assert len(blob) < 0.5 * len(full)


# --- compressed wire envelope -------------------------------------------------


def test_npz_compressed_envelope_roundtrips_and_counts_bytes(tmp_path):
    compressible = {"w": np.zeros((4096,), np.float32),
                    "b": np.ones((64,), np.float32)}
    plain = WeightStore(DiskFolder(str(tmp_path / "plain")))
    packed = WeightStore(DiskFolder(str(tmp_path / "packed")), compress="npz")
    u = NodeUpdate(compressible, num_examples=1, node_id="n", counter=0)
    plain.push(u)
    packed.push(u)
    assert plain.bytes_written > 0 and packed.bytes_written > 0
    assert packed.bytes_written < 0.5 * plain.bytes_written
    pulled = WeightStore(packed.folder).pull_node("n")  # readers sniff format
    np.testing.assert_array_equal(pulled.params["w"], compressible["w"])
    assert peek_meta(packed.folder.get("latest/n"))["node_id"] == "n"


def test_zstd_envelope_gated_or_roundtrips(tmp_path):
    store = WeightStore(InMemoryFolder(), compress="zstd")
    params = {"w": np.zeros((2048,), np.float32)}
    store.push(NodeUpdate(params, num_examples=1, node_id="n", counter=0))
    pulled = WeightStore(store.folder).pull_node("n")
    np.testing.assert_array_equal(pulled.params["w"], params["w"])


def test_compressed_delta_transport_stays_bitwise(tmp_path):
    folder, _store, history = _run_store(tmp_path, "delta", compress="npz")
    pulled = WeightStore(folder).pull_node("n")
    np.testing.assert_array_equal(pulled.params["enc"]["w"], history[-1]["enc"]["w"])


# --- steady-state shape of the hot path --------------------------------------


def test_partial_fedavg_personal_leaves_exact_for_nonf32_models():
    """Personal (non-federated) leaves of int/f64 models must pass through
    bit-exact — never rounded through the f32 flat."""
    from repro.core.strategies import PartialFedAvg

    big = np.asarray([2**53 + 1.0, 7.5], np.float64)  # not f32-representable
    ids = np.asarray([2**40 + 3, 5], np.int64)
    own = NodeUpdate({"enc": {"w": np.ones((4,), np.float32)},
                      "head": big.copy(), "steps": ids.copy()},
                     num_examples=1, node_id="a")
    peer = NodeUpdate({"enc": {"w": np.zeros((4,), np.float32)},
                       "head": big * 0.5, "steps": ids * 0},
                      num_examples=1, node_id="b")
    out = PartialFedAvg(shared_pattern=r"^enc/").aggregate(own, [peer])
    np.testing.assert_allclose(out["enc"]["w"], 0.5)        # federated
    np.testing.assert_array_equal(out["head"], big)         # exact, f64
    np.testing.assert_array_equal(out["steps"], ids)        # exact, int64
    assert out["head"].dtype == np.float64 and out["steps"].dtype == np.int64


def test_leafspec_flatten_rejects_leaf_shape_permutation():
    """Two leaves swapping sizes under the same treedef must not silently
    produce a mislaid flat vector (same total, different offsets)."""
    spec = LeafSpec.of({"a": np.zeros((10, 2), np.float32),
                        "b": np.zeros((2, 10), np.float32),
                        "c": np.zeros((5,), np.float32)})
    permuted = {"a": np.zeros((4,), np.float32),        # 20 → 4
                "b": np.zeros((21,), np.float32),       # 20 → 21
                "c": np.zeros((20,), np.float32)}       # 5 → 20 (total 45 = 45)
    with pytest.raises(ValueError):
        spec.flatten(permuted)
    with pytest.raises(ValueError):
        spec.flatten_into(permuted, spec.empty_flat())


def test_mixed_f16_f32_peers_keep_their_dtypes():
    """Same-structure f16 and f32 models must not share a spec: each peer's
    pulled params keep their native dtype and exact values (regression: the
    interning key once ignored native wire dtypes)."""
    store = WeightStore(InMemoryFolder())
    p16 = {"w": np.linspace(-1, 1, 8, dtype=np.float16)}
    p32 = {"w": np.linspace(-1, 1, 8, dtype=np.float32) * np.float32(0.1)}
    store.push(NodeUpdate(p16, num_examples=1, node_id="h", counter=0))
    store.push(NodeUpdate(p32, num_examples=1, node_id="s", counter=0))
    pulled = {u.node_id: u for u in store.pull()}
    assert pulled["h"].params["w"].dtype == np.float16
    assert pulled["s"].params["w"].dtype == np.float32
    np.testing.assert_array_equal(pulled["h"].params["w"], p16["w"])
    np.testing.assert_array_equal(pulled["s"].params["w"], p32["w"])
    assert pulled["h"].spec.key != pulled["s"].spec.key


def test_sharded_bytes_written_includes_summary_traffic():
    from repro.core.gossip import ShardedFolders, ShardedWeightStore

    store = ShardedWeightStore(
        ShardedFolders(2, factory=lambda g: InMemoryFolder()),
        group_of=lambda nid: int(nid[1]) % 2)
    for i in range(2):
        store.push(NodeUpdate(tree_of([1.0 + i, 0.5]), num_examples=1,
                              node_id=f"n{i}", counter=0))
    stats = store.cache_stats()
    assert stats["summary_bytes_written"] > 0  # refreshes + ring forwards
    # total includes BOTH per-group latest traffic and the summary layer
    assert stats["bytes_written"] > stats["summary_bytes_written"]


def test_node_transport_stats_uniform_shape():
    from repro.core import AsyncFederatedNode
    from repro.core.gossip import ShardedFolders

    flat_node = AsyncFederatedNode(shared_folder=InMemoryFolder(), node_id="a")
    sharded_node = AsyncFederatedNode(
        shared_folder=ShardedFolders(2, factory=lambda g: InMemoryFolder()),
        node_id="b")
    for node in (flat_node, sharded_node):
        node.update_parameters(tree_of([1.0, 0.0]), num_examples=1)
        stats = node.transport_stats()
        assert set(stats) >= {"decode_hits", "decode_misses", "bytes_written"}
        assert stats["bytes_written"] > 0


def test_fedavgm_state_is_flat_vectors():
    strat = FedAvgM()
    own_t, own_f, spec = pair([1.0, 2.0], node="a")
    _, p, spec = pair([0.0, 0.0], node="b", spec=spec)
    strat.aggregate(own_f, [p])
    assert isinstance(strat.x, np.ndarray) and strat.x.ndim == 1
    assert strat.x.size == spec.num_params
    assert isinstance(strat.buf, np.ndarray) and strat.buf.dtype == np.float32


# --- the reader ----------------------------------------------------------------


def _bf16_tree():
    import ml_dtypes

    return {"w": np.linspace(-3, 3, 24, dtype=np.float32).reshape(4, 6)
                 .astype(ml_dtypes.bfloat16),
            "b": np.arange(6, dtype=np.float32).astype(ml_dtypes.bfloat16)}


# name -> blob writer taking compress=; every blob is read in place, its
# members viewed in the stored bytes or in each deflated member inflated once
READER_CASES = {
    "f32": lambda c: serialize_update(NodeUpdate(tree_of([1.5, -0.5]), 3, "n"), compress=c),
    "bf16_widened": lambda c: serialize_update(NodeUpdate(_bf16_tree(), 3, "n"), compress=c),
    "f16": lambda c: serialize_update(NodeUpdate(
        {"w": np.linspace(-2, 2, 30, dtype=np.float16).reshape(5, 6)}, 3, "n"), compress=c),
    "quantized": lambda c: serialize_update_quantized(
        NodeUpdate(tree_of([2.0, 1.0]), 3, "n"), compress=c),
    "zstd": lambda c: serialize_update(NodeUpdate(tree_of([0.25, 2.0]), 3, "n"),
                                       compress="zstd" if c == "none" else c),
    "node_id_slash_dunder": lambda c: serialize_update(
        NodeUpdate(tree_of([1.0, 1.0]), 3, "site/a__b"), compress=c),
    # above the inline and piece sizes: pool tasks and a leaf cut in pieces
    "big_leaf": lambda c: serialize_update(NodeUpdate(
        {"big": np.arange(9 * 2**20, dtype=np.float32) * np.float32(1e-3),
         "small": np.ones((3,), np.float32)}, 3, "n"), compress=c),
    "fortran_leaf": lambda c: serialize_update(NodeUpdate(
        {"w": np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4))},
        3, "n"), compress=c),
}


def _np_load(blob) -> dict:
    """Every member of ``blob`` as np.load reads it, in the blob's order."""
    with np.load(io.BytesIO(maybe_decompress(blob))) as data:
        return {k: data[k] for k in data.files}


def _np_load_meta(blob) -> dict:
    return json.loads(_np_load(blob)["__meta__"].tobytes().decode())


def _np_load_flat(blob, spec) -> np.ndarray:
    """The f32 flat of a full or quantized blob, in ``spec``'s leaf order,
    built from what np.load reads."""
    data, quantized = _np_load(blob), _np_load_meta(blob).get("quantized")
    parts = []
    for path in spec.paths:
        key = path.replace("/", "|")
        if quantized:
            parts.append(data["q|" + key].astype(np.float32) * np.float32(data["s|" + key]))
        else:
            parts.append(data[key].astype(np.float32))
    return np.concatenate([p.reshape(-1) for p in parts])


def _assert_bitwise(a: np.ndarray, b: np.ndarray) -> None:
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.flags.f_contiguous == b.flags.f_contiguous
    assert np.ascontiguousarray(a).tobytes() == np.ascontiguousarray(b).tobytes()


@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_direct_reader_matches_np_load_bitwise(case):
    """A blob read in place, stored or deflated, gives bit for bit the flat
    that np.load's arrays give, and the same spec and meta either way."""
    decoded = []
    for compress in ("none", "npz"):
        blob = READER_CASES[case](compress)
        spec, flat, meta = decode_params_flat(NpzEnvelope(blob), {})
        assert flat.dtype == np.float32
        want = _np_load_flat(blob, spec)
        assert flat.view(np.uint32).tobytes() == want.view(np.uint32).tobytes()
        want_meta = _np_load_meta(blob)
        del want_meta["__order__"], want_meta["__dtypes__"]
        assert meta == want_meta
        decoded.append((spec.key, spec.paths, spec.shapes, spec.dtypes, meta))
    assert decoded[0] == decoded[1]


def _flip_inside(blob: bytes, payload: bytes) -> bytes:
    at = blob.find(payload)
    assert at >= 0 and blob.find(payload, at + 1) < 0
    at += len(payload) // 2
    return blob[:at] + bytes([blob[at] ^ 0x40]) + blob[at + 1:]


@pytest.mark.parametrize("where", ["small_leaf", "big_leaf", "meta", "deflated_leaf",
                                   "deflated_crc"])
def test_direct_reader_rejects_a_flipped_byte(where):
    big = np.arange(2**19, dtype=np.float32) + np.float32(0.5)  # 2 MiB: the pool's
    small = np.asarray([7.25, -1.5, 3.0], np.float32)
    update = NodeUpdate({"big": big, "small": small}, 3, "node-x")
    if where.startswith("deflated"):
        blob = serialize_update(update, compress="npz")
        if where == "deflated_leaf":  # a byte in the middle of big's deflate stream
            zi = zipfile.ZipFile(io.BytesIO(blob)).getinfo("big.npy")
            at = zi.header_offset + 30 + len("big.npy") + 20 + zi.compress_size // 2
        else:  # the CRC-32 in big's directory entry: only the check sees it
            at = blob.rfind(b"big.npy") - 46 + 16
        blob = blob[:at] + bytes([blob[at] ^ 0x40]) + blob[at + 1:]
    else:
        payload = {"small_leaf": small.tobytes(), "big_leaf": big.tobytes(),
                   "meta": b'"node-x"'}[where]
        blob = _flip_inside(serialize_update(update), payload)
    with pytest.raises(zipfile.BadZipFile):
        decode_params_flat(blob, {})


def test_direct_reader_rejects_a_truncated_blob():
    blob = serialize_update(NodeUpdate(
        {"w": np.arange(4096, dtype=np.float32)}, 3, "n"))
    cut = blob.find(np.arange(4096, dtype=np.float32).tobytes()) + 1000
    with pytest.raises(zipfile.BadZipFile):
        decode_params_flat(blob[:cut], {})


def test_non_zip_bytes_raise_as_np_load_does():
    with pytest.raises(ValueError):
        decode_params_flat(b"neither a zip nor an array", {})


def test_direct_reader_int_leaves_still_unsupported():
    from repro.core.serialize import FlatDecodeUnsupported

    blob = serialize_update(NodeUpdate(
        {"ids": np.asarray([2**40 + 3, 7], np.int64),
         "w": np.ones((2,), np.float32)}, 1, "n"))
    env = NpzEnvelope(blob)
    _assert_bitwise(env.view("ids"), _np_load(blob)["ids"])  # in place, exact
    with pytest.raises(FlatDecodeUnsupported):
        decode_params_flat(env, {})


def _np_load_pulled(folder, node: str, spec) -> np.ndarray:
    """What a reader of ``latest/<node>`` must reconstruct, from np.load: the
    full blob's flat, or its delta's entries over its base's flat."""
    blob = folder.get(f"latest/{node}")
    meta = _np_load_meta(blob)
    if "delta_of" not in meta:
        return _np_load_flat(blob, spec)
    flat = _np_load_flat(folder.get(f"base/{node}/{meta['delta_of']}"), spec)
    data = _np_load(blob)
    for i, path in enumerate(spec.paths):
        key, o, n = path.replace("/", "|"), spec.offsets[i], spec.sizes[i]
        if "d|" + key in data:
            flat[o:o + n] = data["d|" + key].reshape(-1)
        else:
            flat[o + data["i|" + key]] = data["v|" + key]
    return flat


@pytest.mark.parametrize("transport,compress", [
    ("full", "none"), ("full", "zstd"), ("quantized", "none"), ("delta", "none"),
    ("full", "npz"),
])
def test_store_pull_counts_decodes_by_reader(transport, compress):
    """Every blob a store pulls is decoded once, and each pulled flat is bit
    for bit what np.load's arrays of the folder's blobs give."""
    from repro.api import connect

    uri = f"memory://flat-reader-{transport}-{compress}"
    writer = connect(uri, transport=transport, compress=compress)
    reader = connect(uri, transport=transport, compress=compress)
    for ctr in range(2):
        for node in ("a", "b"):
            writer.push(NodeUpdate(tree_of([1.0 + ctr, 0.5]), num_examples=1,
                                   node_id=node, counter=ctr))
        pulled = reader.pull()
        assert len(pulled) == 2
        for u in pulled:
            want = _np_load_pulled(writer.folder, u.node_id, u.spec)
            assert u.flat.view(np.uint32).tobytes() == want.view(np.uint32).tobytes()
    assert reader.transport_stats()["decodes"] == 4


def test_concurrent_direct_decodes_share_the_pool():
    """Many threads decoding at once through the one decode pool each get
    their own complete, correct flat."""
    import sys
    import threading

    tree = {"a": np.arange(2**19, dtype=np.float32),
            "b": -np.arange(3 * 2**18, dtype=np.float32),
            "c": np.ones((5,), np.float32)}
    blob = serialize_update(NodeUpdate(tree, 1, "n"))
    _spec, want, _meta = decode_params_flat(blob, {})
    results, errors = [], []

    def work():
        try:
            for _ in range(4):
                results.append(decode_params_flat(blob, {})[1])
        except Exception as e:  # noqa: BLE001 - reported by the assertion
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert len(results) == 48
    for flat in results:
        np.testing.assert_array_equal(flat, want)


# --- the writer -------------------------------------------------------------------


def _wide_bf16(n):
    import ml_dtypes

    return np.linspace(-4, 4, n, dtype=np.float32).astype(ml_dtypes.bfloat16)


def _float8_arrays():
    import ml_dtypes

    return {"e4m3": np.linspace(-8, 8, 3000, dtype=np.float32).astype(ml_dtypes.float8_e4m3fn),
            "e5m2": np.linspace(-8, 8, 1100, dtype=np.float32).astype(
                ml_dtypes.float8_e5m2).reshape(11, 100)}


# name -> arrays as serialize_params's callers hand them over (leaves in their
# own dtype); several span more than one piece at the low pool thresholds
ARRAY_CASES = {
    "zero_d_and_empty": lambda: {"s": np.asarray(2.5, np.float32),
                                 "e": np.zeros((0, 3), np.float32),
                                 "e_bf16": _wide_bf16(0)},
    "fortran": lambda: {"w": np.asfortranarray(
        np.arange(2000, dtype=np.float32).reshape(40, 50))},
    "fortran_bf16": lambda: {"w": np.asfortranarray(_wide_bf16(2000).reshape(40, 50))},
    "strided": lambda: {"w": np.arange(6000, dtype=np.float32).reshape(60, 100)[::2, 1::3],
                        "w_bf16": np.asfortranarray(
                            _wide_bf16(6000).reshape(60, 100))[::3, ::2]},
    "int8_with_f32_scales": lambda: {"q|w": np.arange(10_000).astype(np.int8),
                                     "s|w": np.asarray(np.float32(0.0123))},
    "bf16_multi_piece": lambda: {"w": _wide_bf16(5000).reshape(50, 100),
                                 "b": _wide_bf16(7)},
    "float8": _float8_arrays,
    "other_dtypes": lambda: {"big_endian": np.arange(9, dtype=">f4"),
                             "f64": np.linspace(0, 1, 1500),
                             "bool": np.arange(10) % 3 == 0,
                             "text": np.asarray(["ab", "cde"]),
                             "uint8": np.arange(256, dtype=np.uint8)},
    "datetime_and_bytes": lambda: {
        "when": np.arange("2024-01-01", "2024-03-01", dtype="M8[D]").reshape(4, -1)[:, ::2],
        "took": np.asarray([3, -1, 7], "m8[ms]"),
        "raw": np.asarray([b"ab", b"", b"cde\x00f"])},
    "non_ascii_key": lambda: {"wé|b": np.ones((3,), np.float32)},
}


def _meta_bytes(update, order, dtypes) -> bytes:
    """The __meta__ member serialize_update writes for ``update``."""
    from repro.core.serialize import _update_meta

    return json.dumps({**_update_meta(update), "__order__": order,
                       "__dtypes__": dtypes}).encode()


def _savez(arrays, deflate: bool = False) -> bytes:
    """What np.savez (np.savez_compressed with ``deflate``) writes for
    ``arrays``, each widened as it ships."""
    from repro.core import serialize

    buf = io.BytesIO()
    (np.savez_compressed if deflate else np.savez)(
        buf, **{k: serialize._wire_leaf(a)[0] for k, a in arrays.items()})
    return buf.getvalue()


def _savez_of(blob) -> bytes:
    """np.savez (or np.savez_compressed, for a deflated blob) over what
    np.load reads from ``blob``."""
    blob = maybe_decompress(blob)
    deflated = zipfile.ZipFile(io.BytesIO(blob)).infolist()[0].compress_type
    return _savez(_np_load(blob), deflate=deflated == zipfile.ZIP_DEFLATED)


@pytest.fixture(params=["inline", "pool"])
def writer_sizes(request, monkeypatch):
    """The writer as it is (small members on the caller's thread), or with
    every piece sent to the pool and cut at 4 KiB, so the piece and CRC
    combining paths run at test sizes. The clock is frozen at another date:
    ``zipfile`` stamps what ``ZipFile.write`` adds with it, but not what
    ``ZipFile.open(name, "w")`` adds, as np.savez does, so the bytes must
    not depend on it."""
    import time

    from repro.core import serialize

    frozen = time.struct_time((2031, 7, 9, 13, 14, 15, 2, 190, 0))
    monkeypatch.setattr(time, "localtime", lambda *_: frozen)
    if request.param == "pool":
        monkeypatch.setattr(serialize, "_INLINE_BYTES", 0)
        monkeypatch.setattr(serialize, "_PIECE_BYTES", 1 << 12)
    return request.param


def _with_deflated(cases) -> list[str]:
    """Each case stored, then each deflated: ``<case>+npz``."""
    return [c + how for how in ("", "+npz") for c in cases]


@pytest.mark.parametrize("case", _with_deflated(sorted(ARRAY_CASES)))
def test_stored_writer_matches_np_savez_bytes(case, writer_sizes):
    """The writer gives np.savez's bytes (np.savez_compressed's, deflated),
    and the reader reads back from them what np.load does."""
    from repro.core import serialize

    case, _, how = case.partition("+")
    deflate = how == "npz"
    arrays = ARRAY_CASES[case]()
    arrays["__meta__"] = np.frombuffer(b'{"__order__": [], "x": 1}', np.uint8)
    blob = serialize._write_stored(dict(arrays), deflate=deflate)
    assert blob == _savez(arrays, deflate)
    env, want = NpzEnvelope(blob), _np_load(blob)
    got = env.read(env.files)
    assert list(got) == list(want)
    for key in want:
        _assert_bitwise(got[key], want[key])
        assert got[key].flags.writeable
        _assert_bitwise(env.view(key), want[key])


@pytest.mark.parametrize("case", _with_deflated(sorted(READER_CASES) + ["fleet_meta_only"]))
def test_every_envelope_writer_matches_the_np_savez_path(case, writer_sizes, monkeypatch):
    """Each blob writer's envelope is byte for byte what np.savez (deflated:
    np.savez_compressed) writes for the arrays it was handed; ``peek_meta``
    and ``deserialize_params`` read from it what np.load reads."""
    from repro.core import serialize

    case, _, compress = case.partition("+")
    compress = compress or "none"
    if case == "fleet_meta_only":  # no arrays: a lone uint8 __meta__
        def write(c):
            return serialize.serialize_fleet_blob("heartbeat", {"t": 1}, compress=c)
    else:
        write = READER_CASES[case]
    handed, write_stored = [], serialize._write_stored

    def recording(arrays, *, deflate=False):
        handed.append(_savez(arrays, deflate))
        return write_stored(arrays, deflate=deflate)

    monkeypatch.setattr(serialize, "_write_stored", recording)
    blob = write(compress)
    assert len(handed) == 1 and maybe_decompress(blob) == handed[0]
    data, meta = _np_load(blob), _np_load_meta(blob)
    assert peek_meta(blob) == meta
    params, rest = deserialize_params(blob)
    assert rest == {k: v for k, v in meta.items() if k not in ("__order__", "__dtypes__")}
    leaves = {path_str(p).replace("/", "|"): leaf
              for p, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert sorted(leaves) == sorted(meta["__order__"])
    for key, leaf in leaves.items():
        _assert_bitwise(np.asarray(leaf).astype(data[key].dtype), data[key])


def _npy_file(arr) -> bytes:
    buf = io.BytesIO()
    np.lib.format.write_array(buf, arr)
    return buf.getvalue()


def test_stored_writer_hands_what_it_cannot_hold_to_np_savez():
    """An object array (np.savez would pickle it) or a structured one is
    refused by name, stored or deflated; and the reader refuses members this
    writer cannot have written: pickled, a format 2.0 header, not .npy."""
    from repro.core import serialize

    for key, arr in (("rec", np.zeros(3, dtype=[("a", "<f4"), ("b", "<i2")])),
                     ("o", np.asarray([1, "a"], dtype=object))):
        for deflate in (False, True):
            with pytest.raises(ValueError, match=repr(key)):
                serialize._write_stored({key: arr}, deflate=deflate)
    with pytest.raises(ValueError, match="'o'"):
        serialize._pack_npz({"o": np.asarray([1, "a"], dtype=object)}, ["o"], {}, None)
    meta = np.frombuffer(b'{"__order__": ["x"], "__dtypes__": {}}', np.uint8)
    pickled = io.BytesIO()
    np.savez(pickled, x=np.asarray([1, "a"], dtype=object), __meta__=meta)
    v2 = io.BytesIO()
    with zipfile.ZipFile(v2, "w") as zf:
        zf.writestr("__meta__.npy", _npy_file(meta))
        with zf.open("x.npy", "w") as f:
            np.lib.format.write_array(f, np.ones(3, np.float32), version=(2, 0))
    text = io.BytesIO()
    with zipfile.ZipFile(text, "w") as zf:
        zf.writestr("__meta__.npy", _npy_file(meta))
        zf.writestr("x.txt", b"not an array")
    for blob in (pickled.getvalue(), v2.getvalue(), text.getvalue()):
        with pytest.raises(ValueError):
            deserialize_params(blob)


@pytest.mark.parametrize("fallback", ["zip64_member", "deflate", "zip64_deflated_member"])
def test_fallbacks_write_np_savez_bytes_and_count_as_savez(fallback, monkeypatch):
    """A member too big for the directory's 32-bit sizes (zipfile's
    ZIP64_LIMIT, lowered here), stored or deflated, and a deflated envelope
    are written as np.savez (np.savez_compressed) writes them, and read back
    in place to what np.load reads."""
    deflate = fallback != "zip64_member"
    update = NodeUpdate({"w": np.arange(600, dtype=np.float32), "b": _wide_bf16(9)},
                        3, "n")
    if fallback.startswith("zip64"):
        monkeypatch.setattr(zipfile, "ZIP64_LIMIT", 2000)
    blob = serialize_update(update, compress="npz" if deflate else "none")
    arrays = {"b": _wide_bf16(9), "w": np.arange(600, dtype=np.float32)}
    arrays["__meta__"] = np.frombuffer(_meta_bytes(update, ["b", "w"], {"b": "bfloat16"}),
                                       np.uint8)
    assert blob == _savez(arrays, deflate)
    big = zipfile.ZipFile(io.BytesIO(blob)).getinfo("w.npy")
    assert big.file_size > 2000 and big.compress_type == (
        zipfile.ZIP_DEFLATED if deflate else zipfile.ZIP_STORED)
    spec, flat, _meta = decode_params_flat(blob, {})
    assert flat.view(np.uint32).tobytes() == _np_load_flat(blob, spec).view(np.uint32).tobytes()


def test_offsets_past_zip64_limit_are_written_directly(monkeypatch):
    """A blob over zipfile's ZIP64_LIMIT (lowered here), with every member
    under it: zip64 offsets in the directory and a zip64 end record, as
    np.savez writes them, and the reader reads it."""
    monkeypatch.setattr(zipfile, "ZIP64_LIMIT", 1500)
    tree = {f"l{i}": np.arange(150, dtype=np.float32) * np.float32(i) for i in range(12)}
    update = NodeUpdate(tree, 3, "n")
    blob = serialize_update(update)
    order = sorted(tree)
    assert blob == _savez({**{k: tree[k] for k in order}, "__meta__": np.frombuffer(
        _meta_bytes(update, order, {}), np.uint8)})
    assert len(blob) > 1500 and zipfile.ZipFile(io.BytesIO(blob)).testzip() is None
    spec, flat, _meta = decode_params_flat(blob, {})
    np.testing.assert_array_equal(spec.unflatten(flat)["l11"], tree["l11"])


def _codec_params(ctr: int, node: str) -> dict:
    """Big enough that the delta codecs ship sparse deltas after the first
    push: three entries of ``w`` change a round."""
    w = np.linspace(-1, 1, 4096, dtype=np.float32).reshape(64, 64) + np.float32(node == "b")
    w[ctr, :3] += np.float32(ctr)
    return {"w": w, "b": _wide_bf16(512)}


# transport -> flat decodes a reader makes over three rounds of two nodes:
# every full blob it pulls, and for the delta codecs each node's base
CODEC_DIRECT_DECODES = {"full": 6, "quantized": 6, "delta": 4, "delta_q": 4, "topk": 4}


@pytest.mark.parametrize("transport", sorted(CODEC_DIRECT_DECODES))
def test_every_codec_writes_np_savez_bytes(transport, monkeypatch):
    """After every round each blob in the folder is what np.savez writes over
    what np.load reads from it, and a reader decodes each full blob once."""
    from repro.api import connect
    from repro.core import transport as transport_mod

    uri = f"memory://writers-{transport}"
    writer, reader = connect(uri, transport=transport), connect(uri, transport=transport)
    flat_decodes, decode = [], transport_mod.decode_params_flat

    def counting(env, specs):
        flat_decodes.append(specs is reader._ctx.specs)
        return decode(env, specs)

    monkeypatch.setattr(transport_mod, "decode_params_flat", counting)
    for ctr in range(3):
        for node in ("a", "b"):
            writer.push(NodeUpdate(_codec_params(ctr, node), num_examples=1,
                                   node_id=node, counter=ctr, timestamp=float(ctr)))
        keys = sorted(writer.folder.keys())
        for key in keys:
            blob = writer.folder.get(key)
            assert blob == _savez_of(blob), (ctr, key)
        assert len(reader.pull()) == 2
    if transport not in ("full", "quantized"):
        assert any("delta_of" in peek_meta(writer.folder.get(key)) for key in keys)
    assert sum(flat_decodes) == CODEC_DIRECT_DECODES[transport]


def test_full_transport_node_on_bf16_model_writes_directly():
    """A node's bf16 update is shipped as np.savez writes the tree widened to
    f32."""
    from repro.core import AsyncFederatedNode

    folder = InMemoryFolder()
    node = AsyncFederatedNode(shared_folder=folder, node_id="a")
    tree = {"w": _wide_bf16(64).reshape(8, 8), "b": _wide_bf16(8)}
    for _ in range(2):
        node.update_parameters(tree, num_examples=1)
    blob = folder.get("latest/a")
    assert blob == _savez({"b": tree["b"].astype(np.float32),
                           "w": tree["w"].astype(np.float32),
                           "__meta__": _np_load(blob)["__meta__"]})


def test_crc32_combine_matches_zlib():
    import zlib

    from repro.core.serialize import _crc32_combine

    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 5000, dtype=np.uint8).tobytes()
    for cut in (0, 1, 7, 2500, 4999, 5000):
        a, b = data[:cut], data[cut:]
        assert _crc32_combine(zlib.crc32(a), zlib.crc32(b), len(b)) == zlib.crc32(data)


def test_concurrent_stored_writes_share_the_pool():
    """Many threads writing through the one codec pool at once each get the
    np.savez bytes of their own update."""
    import sys
    import threading

    from repro.core import serialize

    def update(i):
        return NodeUpdate({"a": np.arange(2**19, dtype=np.float32) * np.float32(i),
                           "b": _wide_bf16(3 * 2**18), "c": np.ones((5,), np.float32)},
                          1, f"n{i}")

    want = {}
    for i in range(6):
        arrays = {"a": update(i).params["a"], "b": update(i).params["b"],
                  "c": update(i).params["c"]}
        arrays["__meta__"] = np.frombuffer(
            _meta_bytes(update(i), ["a", "b", "c"], {"b": "bfloat16"}), np.uint8)
        want[i] = _savez(arrays)
    results, errors = [], []

    def work(i):
        try:
            for _ in range(3):
                results.append((i, serialize_update(update(i))))
        except Exception as e:  # noqa: BLE001 - reported by the assertion
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i % 6,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert len(results) == 36
    for i, blob in results:
        assert blob == want[i]
    i, blob = results[0]
    np.testing.assert_array_equal(NpzEnvelope(blob).read(["a"])["a"], update(i).params["a"])
