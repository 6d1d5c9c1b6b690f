import jax
import ml_dtypes
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core.serialize import _INLINE_BYTES, FlatUpdate, NodeUpdate
from repro.core.strategies import (
    _CHUNK,
    STRATEGIES,
    FedAdam,
    FedAsync,
    FedAvg,
    FedAvgM,
    FedBuff,
    PartialFedAvg,
    get_strategy,
)
from repro.core.tree import LeafSpec, tree_allclose


def upd(val, n=10, node="x", counter=0):
    params = {"layer": {"w": np.full((3, 2), float(val), np.float32)},
              "head": np.full((4,), float(val) * 2, np.float32)}
    return NodeUpdate(params, num_examples=n, node_id=node, counter=counter)


def test_fedavg_weighted():
    out = FedAvg().aggregate(upd(0.0, n=100), [upd(4.0, n=300, node="y")])
    assert np.allclose(out["layer"]["w"], 3.0)


@settings(max_examples=25, deadline=None)
@given(vals=st.lists(st.floats(-5, 5), min_size=1, max_size=5),
       ns=st.lists(st.integers(1, 1000), min_size=5, max_size=5))
def test_fedavg_bounds(vals, ns):
    """FedAvg output within [min,max] of inputs for any example counts."""
    own = upd(vals[0], n=ns[0])
    peers = [upd(v, n=ns[i + 1], node=f"p{i}") for i, v in enumerate(vals[1:])]
    out = FedAvg().aggregate(own, peers)
    assert out["layer"]["w"].min() >= min(vals) - 1e-5
    assert out["layer"]["w"].max() <= max(vals) + 1e-5


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_all_strategies_identity_on_identical(name):
    """Any strategy aggregating identical params must return those params
    (FedBuff returns own params before its buffer fills — same thing)."""
    kwargs = {"buffer_size": 2} if name == "fedbuff" else {}
    strat = get_strategy(name, **kwargs)
    own = upd(1.5)
    peers = [upd(1.5, node="p0"), upd(1.5, node="p1")]
    out = strat.aggregate(own, peers)
    assert tree_allclose(out, own.params, rtol=1e-4, atol=1e-4), name


def test_fedavgm_momentum_accumulates():
    strat = FedAvgM(server_lr=1.0, momentum=0.9)
    own = upd(1.0)
    out1 = strat.aggregate(own, [upd(0.0, node="p")])
    # x=1, avg=0.5 → delta=0.5 → buf=0.5 → x=0.5
    assert np.allclose(out1["layer"]["w"], 0.5)
    out2 = strat.aggregate(upd(0.5), [upd(0.5, node="p")])
    # avg=0.5, delta=0 → buf=0.45 → x=0.05: momentum keeps moving
    assert np.allclose(out2["layer"]["w"], 0.05, atol=1e-6)


def test_fedadam_moves_toward_average():
    strat = FedAdam(server_lr=0.1)
    out = strat.aggregate(upd(1.0), [upd(0.0, node="p")])
    assert np.all(out["layer"]["w"] < 1.0)


def test_fedasync_staleness_discounts():
    fresh = FedAsync(alpha=0.5, staleness_fn="poly", a=1.0)
    own = upd(0.0, counter=10)
    out_fresh = fresh.aggregate(own, [upd(1.0, node="p", counter=10)])
    out_stale = fresh.aggregate(own, [upd(1.0, node="p", counter=0)])
    # stale peer (staleness 10) must move us less than a fresh peer
    assert out_stale["layer"]["w"][0, 0] < out_fresh["layer"]["w"][0, 0]
    assert np.allclose(out_fresh["layer"]["w"], 0.5)  # α·s(0)=0.5 mix


def test_fedbuff_waits_for_buffer():
    strat = FedBuff(buffer_size=3)
    own = upd(0.0)
    out1 = strat.aggregate(own, [])
    assert tree_allclose(out1, own.params)  # buffer has 1 < 3 → own params
    out2 = strat.aggregate(own, [upd(3.0, node="p0"), upd(6.0, node="p1")])
    assert np.allclose(out2["layer"]["w"], 3.0)  # buffer full → mean


def test_fedbuff_dedups_by_counter():
    strat = FedBuff(buffer_size=3)
    own = upd(0.0)
    stale_peer = upd(9.0, node="p0", counter=0)
    strat.aggregate(own, [stale_peer])
    out = strat.aggregate(own, [stale_peer])  # same counter → not re-buffered
    assert tree_allclose(out, own.params)


def test_partial_fedavg_only_shares_matching():
    strat = PartialFedAvg(shared_pattern=r"^layer/")
    out = strat.aggregate(upd(0.0), [upd(2.0, node="p")])
    assert np.allclose(out["layer"]["w"], 1.0)   # federated
    assert np.allclose(out["head"], 0.0)         # personal, untouched


def test_kernel_backed_fedavg_matches():
    plain = FedAvg().aggregate(upd(1.0, n=10), [upd(5.0, n=30, node="p")])
    kern = FedAvg(use_kernel=True).aggregate(upd(1.0, n=10), [upd(5.0, n=30, node="p")])
    assert tree_allclose(plain, kern, rtol=1e-5, atol=1e-5)


# --- async strategy semantics (FedAsync staleness, FedBuff buffering) --------


@pytest.mark.parametrize("fn", ["poly", "hinge", "const"])
def test_fedasync_discount_monotone_nonincreasing(fn):
    """s(staleness) must never grow with staleness, for every discount family."""
    strat = FedAsync(staleness_fn=fn, a=0.5, b=4)
    discounts = [strat._discount(s) for s in range(0, 20)]
    assert all(d1 >= d2 - 1e-12 for d1, d2 in zip(discounts, discounts[1:])), discounts
    assert all(0.0 < d <= 1.0 for d in discounts)


def test_fedasync_poly_strictly_decreasing_const_flat():
    poly = FedAsync(staleness_fn="poly", a=0.5)
    assert poly._discount(0) > poly._discount(1) > poly._discount(5)
    const = FedAsync(staleness_fn="const")
    assert const._discount(0) == const._discount(100) == 1.0


def test_fedasync_hinge_flat_then_decaying():
    hinge = FedAsync(staleness_fn="hinge", a=0.5, b=4)
    assert hinge._discount(0) == hinge._discount(4) == 1.0
    assert hinge._discount(5) < 1.0
    assert hinge._discount(10) < hinge._discount(5)


def test_fedasync_mixing_bounded_by_alpha():
    """Aggregate must stay within [own, own + α·(peer − own)] per peer."""
    strat = FedAsync(alpha=0.3, staleness_fn="const")
    out = strat.aggregate(upd(0.0), [upd(10.0, node="p")])
    assert np.allclose(out["layer"]["w"], 3.0)  # α · s(0) = 0.3 of the gap


def test_fedbuff_rebuffers_newer_counter():
    """A peer's *newer* update re-enters the buffer after a flush; replays of
    the same counter do not."""
    strat = FedBuff(buffer_size=2)
    own = upd(0.0)
    out = strat.aggregate(own, [upd(4.0, node="p", counter=0)])
    assert np.allclose(out["layer"]["w"], 2.0)  # flushed at threshold
    # replay of counter 0 → ignored, buffer only has own → own params back
    out = strat.aggregate(own, [upd(4.0, node="p", counter=0)])
    assert tree_allclose(out, own.params)
    # the peer progressed to counter 1 → buffered again → flush
    out = strat.aggregate(own, [upd(8.0, node="p", counter=1)])
    assert np.allclose(out["layer"]["w"], 4.0)


def test_fedbuff_counts_distinct_nodes_not_updates():
    strat = FedBuff(buffer_size=3)
    own = upd(0.0)
    # two successive updates from the same peer must not fill a 3-buffer
    strat.aggregate(own, [upd(1.0, node="p", counter=0)])
    out = strat.aggregate(own, [upd(2.0, node="p", counter=1)])
    assert tree_allclose(out, own.params)  # still only {own, p} buffered
    out = strat.aggregate(own, [upd(3.0, node="q", counter=0)])
    assert not tree_allclose(out, own.params)  # third distinct node → flush


# --- FedAsync epoch-gap discount (elastic-fleet churn) -----------------------


def eupd(val, *, node="x", counter=0, lease_epoch=0, n=10):
    u = upd(val, n=n, node=node, counter=counter)
    u.lease_epoch = lease_epoch
    return u


def test_fedasync_epoch_gap_damps_adopted_peers():
    """A peer running at a higher lease epoch (adopted after worker death)
    mixes in with weight α·(1+gap)^(-epoch_a); const staleness isolates the
    epoch term."""
    strat = FedAsync(alpha=0.4, staleness_fn="const", epoch_a=1.0)
    base = strat.aggregate(eupd(0.0), [eupd(10.0, node="p")])
    assert np.allclose(base["layer"]["w"], 4.0)  # α alone
    damped = strat.aggregate(eupd(0.0), [eupd(10.0, node="p", lease_epoch=1)])
    assert np.allclose(damped["layer"]["w"], 2.0)  # α/(1+1)
    more = strat.aggregate(eupd(0.0), [eupd(10.0, node="p", lease_epoch=3)])
    assert np.allclose(more["layer"]["w"], 1.0)  # α/(1+3)


def test_fedasync_epoch_gap_is_one_sided():
    """Only peers AHEAD in epochs are damped: the adopted node itself (own
    epoch high, peers at 0) absorbs the live consensus at full strength."""
    strat = FedAsync(alpha=0.4, staleness_fn="const", epoch_a=1.0)
    own = eupd(0.0, lease_epoch=2)
    out = strat.aggregate(own, [eupd(10.0, node="p", lease_epoch=0)])
    assert np.allclose(out["layer"]["w"], 4.0)  # no damping


def test_fedasync_epoch_gap_disabled_and_backcompat():
    """epoch_a=0 disables the term; gap-0 updates aggregate bit-identically
    to a strategy that predates lease epochs."""
    off = FedAsync(alpha=0.4, staleness_fn="const", epoch_a=0.0)
    out = off.aggregate(eupd(0.0), [eupd(10.0, node="p", lease_epoch=5)])
    assert np.allclose(out["layer"]["w"], 4.0)
    legacy = FedAsync(alpha=0.4, staleness_fn="const")
    a = legacy.aggregate(upd(0.0), [upd(10.0, node="p")])
    b = legacy.aggregate(eupd(0.0), [eupd(10.0, node="p", lease_epoch=0)])
    assert np.array_equal(a["layer"]["w"], b["layer"]["w"])


def test_fedasync_epoch_gap_composes_with_staleness():
    strat = FedAsync(alpha=0.8, staleness_fn="poly", a=1.0, epoch_a=1.0)
    own = eupd(0.0, counter=3)
    peer = eupd(10.0, node="p", counter=1, lease_epoch=1)
    out = strat.aggregate(own, [peer])
    # α · (1+staleness=2)^(-1) · (1+gap=1)^(-1) = 0.8/3/2
    assert np.allclose(out["layer"]["w"], 10.0 * 0.8 / 6.0, rtol=1e-5)


# --- the one-pass combine: on the caller's thread and on the codec pool -------

BF16 = np.dtype(ml_dtypes.bfloat16)
# the smallest leaf whose chunk goes to the codec pool: its f32 accumulator
# holds _INLINE_BYTES
POOL_LEAF = _INLINE_BYTES // 4
COMBINE_KWARGS = {"fedbuff": {"buffer_size": 2},
                  "partial_fedavg": {"shared_pattern": r"^enc/"}}


def combine_updates(tree, spec, flats, r=0):
    """Own's tree and peers' f32 flats, as a node pulls them; ``r`` moves
    every counter on, so FedBuff takes each call's peers as fresh."""
    own = NodeUpdate(tree, num_examples=30, node_id="me", counter=5 + r)
    peers = [FlatUpdate(f, spec, num_examples=10 + 7 * i, node_id=f"p{i}", counter=c + r)
             for i, (f, c) in enumerate(zip(flats, (4, 2)))]
    return own, peers


def as_f32(tree):
    return {k: as_f32(v) if isinstance(v, dict) else np.asarray(v, np.float32)
            for k, v in tree.items()}


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_pool_and_inline_combines_agree_bitwise(name):
    """A leaf just above the pool's threshold goes out as one chunk; the same
    numbers cut into two leaves below it run on the caller's thread. Every
    strategy gives the same bits both ways, and only the first used the pool."""
    rng = np.random.default_rng(7)
    n, cut = POOL_LEAF + 1, (POOL_LEAF + 1) // 2
    w = rng.standard_normal(n, dtype=np.float32).astype(BF16)
    head = rng.standard_normal(5, dtype=np.float32)
    flats = [rng.standard_normal(n + 5, dtype=np.float32) for _ in range(2)]
    outs, chunks = {}, {}
    for label, tree in (("pool", {"enc": {"w": w}, "head": head}),
                        ("inline", {"enc": {"w0": w[:cut], "w1": w[cut:]}, "head": head})):
        spec = LeafSpec.of(tree)
        strat = get_strategy(name, **COMBINE_KWARGS.get(name, {}))
        outs[label] = as_f32(strat.aggregate(*combine_updates(tree, spec, flats)))
        chunks[label] = strat.pool_chunks
    pool, inline = outs["pool"], outs["inline"]
    np.testing.assert_array_equal(
        pool["enc"]["w"], np.concatenate([inline["enc"]["w0"], inline["enc"]["w1"]]))
    np.testing.assert_array_equal(pool["head"], inline["head"])
    assert chunks == {"pool": 1, "inline": 0}


@pytest.mark.parametrize("dtype", [BF16, np.dtype(np.float32)], ids=["bf16", "f32"])
def test_fedavg_within_f32_rounding_of_the_float64_mean(dtype):
    """Own's leaves in ``dtype``, two peers' f32 flats. Each element FedAvg
    returns is ``dtype``'s rounding of an f32 sum within one f32 rounding per
    operation of the float64 weighted mean: K coefficients, K products and
    K − 1 sums, (K + 1)·2⁻²⁴·Σ_k|c_k·x_k| with K = 3."""
    rng = np.random.default_rng(11)
    tree = {"b": rng.standard_normal(7, dtype=np.float32).astype(dtype),
            "w": rng.standard_normal(POOL_LEAF + 9, dtype=np.float32).astype(dtype)}
    spec = LeafSpec.of(tree)
    flats = [rng.standard_normal(spec.num_params, dtype=np.float32) for _ in range(2)]
    own, peers = combine_updates(tree, spec, flats)
    strat = FedAvg()
    out = strat.aggregate(own, peers)
    assert strat.pool_chunks == 1
    weights = np.asarray([30.0, 10.0, 17.0]) / 57.0
    for i, key in enumerate(("b", "w")):
        o, n = int(spec.offsets[i]), spec.sizes[i]
        xs = [np.asarray(tree[key], np.float64)] + [f[o:o + n].astype(np.float64) for f in flats]
        mean = sum(c * x for c, x in zip(weights, xs))
        tol = 4 * 2.0 ** -24 * sum(abs(c * x) for c, x in zip(weights, xs))
        lo = (mean - tol).astype(np.float32).astype(dtype)
        hi = (mean + tol).astype(np.float32).astype(dtype)
        got = out[key]
        assert got.dtype == dtype
        assert np.all((lo <= got) & (got <= hi)), key


def test_combine_cuts_big_leaves_into_chunks_and_keeps_odd_leaves_whole():
    """A leaf of two chunks and three elements sends two chunks to the pool
    and runs its tail inline; a leaf just over the threshold sends one; odd
    sizes (0-d, empty, 21, one under the threshold) run inline. Every leaf
    equals the f32 sum taken in order and cast to its dtype: int and f64
    leaves cast in and back out."""
    rng = np.random.default_rng(3)
    layout = {"a": ((2 * _CHUNK + 3,), BF16), "b": ((POOL_LEAF + 1,), np.float16),
              "c": ((7, 3), np.int32), "d": ((), np.float64), "e": ((0,), np.float32),
              "f": ((POOL_LEAF - 1,), np.float32)}
    tree = {k: (rng.standard_normal(s, dtype=np.float32) * 50).astype(d)
            for k, (s, d) in layout.items()}
    spec = LeafSpec.of(tree)
    flats = [rng.standard_normal(spec.num_params, dtype=np.float32) * 50 for _ in range(2)]
    own, peers = combine_updates(tree, spec, flats)
    strat = FedAvg()
    out = strat.aggregate(own, peers)
    assert strat.pool_chunks == 3
    c = np.asarray([30, 10, 17], np.float32)
    c /= c.sum()
    for i, (key, (shape, dtype)) in enumerate(layout.items()):
        o, n = int(spec.offsets[i]), spec.sizes[i]
        want = np.asarray(tree[key], np.float32).reshape(-1) * c[0]
        for ck, f in zip(c[1:], flats):
            want = want + f[o:o + n] * ck
        got = out[key]
        assert got.dtype == np.dtype(dtype) and got.shape == shape, key
        np.testing.assert_array_equal(got.reshape(-1), want.astype(dtype), err_msg=key)


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_aggregate_returns_fresh_leaves_of_the_spec_dtypes(name):
    """Leaves in the spec's dtypes and shapes, sharing no memory with a
    peer's flat, with own's leaves, or with the previous call's result."""
    rng = np.random.default_rng(5)
    tree = {"enc": {"w": rng.standard_normal((4, 3), dtype=np.float32).astype(BF16)},
            "h16": rng.standard_normal(6, dtype=np.float32).astype(np.float16),
            "head": rng.standard_normal(5, dtype=np.float32)}
    spec = LeafSpec.of(tree)
    flats = [rng.standard_normal(spec.num_params, dtype=np.float32) for _ in range(2)]
    strat = get_strategy(name, **COMBINE_KWARGS.get(name, {}))
    results = []
    for r in range(2):
        own, peers = combine_updates(tree, spec, flats, r)
        leaves = jax.tree.leaves(strat.aggregate(own, peers))
        theirs = [*jax.tree.leaves(tree), *flats]
        for leaf, shape, dtype in zip(leaves, spec.shapes, spec.dtypes):
            assert leaf.dtype == dtype and leaf.shape == shape
            assert not any(np.shares_memory(leaf, x) for x in theirs)
        results.append(leaves)
    assert not any(np.shares_memory(a, b) for a in results[0] for b in results[1])


@pytest.mark.parametrize("name", ["fedavg", "partial_fedavg"])
def test_reuse_output_writes_the_same_leaves_each_call(name):
    """Under ``reuse_output`` each call writes the strategy's own leaves and
    gives what a fresh strategy gives."""
    rng = np.random.default_rng(9)
    tree = {"enc": {"w": rng.standard_normal(POOL_LEAF, dtype=np.float32).astype(BF16)},
            "head": rng.standard_normal(5, dtype=np.float32)}
    spec = LeafSpec.of(tree)
    kwargs = COMBINE_KWARGS.get(name, {})
    strat = get_strategy(name, reuse_output=True, **kwargs)
    first = jax.tree.leaves(strat.aggregate(*combine_updates(tree, spec, [
        rng.standard_normal(spec.num_params, dtype=np.float32) for _ in range(2)])))
    flats = [rng.standard_normal(spec.num_params, dtype=np.float32) for _ in range(2)]
    second = jax.tree.leaves(strat.aggregate(*combine_updates(tree, spec, flats)))
    assert all(a is b for a, b in zip(first, second))
    fresh = jax.tree.leaves(get_strategy(name, **kwargs).aggregate(
        *combine_updates(tree, spec, flats)))
    for a, b in zip(second, fresh):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("n, pooled", [(POOL_LEAF - 1, False), (POOL_LEAF, True)],
                         ids=["below", "above"])
def test_node_counts_the_chunks_sent_to_the_pool(n, pooled):
    """``aggregate.pool_chunks`` is 0 when the combine ran on the caller's
    thread and counts the chunks otherwise."""
    from repro.core import AsyncFederatedNode, InMemoryFolder
    from repro.core.telemetry import Telemetry

    folder = InMemoryFolder()
    AsyncFederatedNode(shared_folder=folder, node_id="peer").update_parameters(
        {"w": np.full(n, 2.0, np.float32)}, num_examples=1)
    tel = Telemetry(enabled=True)
    node = AsyncFederatedNode(shared_folder=folder, node_id="me", telemetry=tel)
    out = node.update_parameters({"w": np.zeros(n, np.float32)}, num_examples=1)
    np.testing.assert_array_equal(out["w"], 1.0)
    assert tel.snapshot()["counters"]["aggregate.pool_chunks"] == (1 if pooled else 0)
