"""The store-native observability plane (``repro.core.telemetry``).

Covers the span/counter flight recorder (ring bound, per-phase aggregates,
near-zero disabled path), the ``obs/`` blob family's hygiene (state-hash
exclusion on both store kinds, GC survival, URI round-trips), the node/store
instrumentation seams, thread-safety of ``PipelineStats`` (the regression the
lock fixes), the bounded ``FederatedCallback.history``, the Chrome
trace-event export schema, and the fleet-level rollups an 8-node soak
assembles from blobs alone.
"""
import json
import logging
import sys
import threading
import time
import uuid

import numpy as np
import pytest

from repro.core import (
    AsyncFederatedNode,
    CachingFolder,
    FederatedCallback,
    FleetSpec,
    InMemoryFolder,
    PipelineStats,
    ShardedFolders,
    ShardedWeightStore,
    SpanRecorder,
    Telemetry,
    WeightStore,
    chrome_trace,
    collect_obs,
    deserialize_obs_blob,
    run_fleet_local,
    serialize_obs_blob,
    telemetry_rollups,
)
from repro.api import connect
from repro.core.telemetry import _NULL_SPAN, env_enabled


def _params(n=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal(n).astype(np.float32)}


# --------------------------------------------------------------------------
# SpanRecorder / Telemetry core
# --------------------------------------------------------------------------


class TestSpanRecorder:
    def test_records_events_and_aggregates(self):
        rec = SpanRecorder(capacity=64)
        with rec.span("pull"):
            pass
        with rec.span("pull"):
            pass
        with rec.span("push"):
            pass
        assert len(rec) == 3
        stats = rec.phase_stats()
        assert stats["pull"]["count"] == 2
        assert stats["push"]["count"] == 1
        assert stats["pull"]["min_s"] <= stats["pull"]["max_s"]
        assert stats["pull"]["total_s"] >= 2 * stats["pull"]["min_s"]

    def test_ring_is_bounded_but_aggregates_are_not(self):
        rec = SpanRecorder(capacity=8)
        for _ in range(30):
            with rec.span("x"):
                pass
        assert len(rec) == 8  # ring holds only the most recent events
        assert rec.dropped == 22
        assert rec.total_recorded == 30
        assert rec.phase_stats()["x"]["count"] == 30  # aggregates fold all

    def test_drain_empties_ring_but_keeps_aggregates(self):
        rec = SpanRecorder(capacity=8)
        with rec.span("x"):
            pass
        events = rec.drain()
        assert [e[0] for e in events] == ["x"]
        assert len(rec) == 0
        assert rec.drain() == []
        assert rec.phase_stats()["x"]["count"] == 1

    def test_injected_clock(self):
        t = [0.0]
        rec = SpanRecorder(capacity=8, clock=lambda: t[0])
        span = rec.span("x")
        span.__enter__()
        t[0] = 2.5
        span.__exit__(None, None, None)
        (name, t0, dur, parent, round_), = rec.drain()
        assert (name, t0, dur, parent, round_) == ("x", 0.0, 2.5, None, 0)


    def test_self_time_excludes_children(self):
        t = [0.0]
        rec = SpanRecorder(capacity=8, clock=lambda: t[0])
        with rec.span("outer", round_=4):
            t[0] = 1.0
            with rec.span("inner", round_=9):  # a child shares its parent's round
                t[0] = 3.0
            t[0] = 3.5
        stats = rec.phase_stats()
        assert stats["outer"]["total_s"] == 3.5 and stats["outer"]["self_s"] == 1.5
        assert stats["inner"]["total_s"] == stats["inner"]["self_s"] == 2.0
        inner, outer = rec.drain()
        assert inner == ("inner", 1.0, 2.0, "outer", 4)
        assert outer == ("outer", 0.0, 3.5, None, 4)

    def test_each_thread_nests_its_own_spans(self):
        rec = SpanRecorder()

        def other():
            with rec.span("other", round_=2):
                time.sleep(0.01)

        with rec.span("outer", round_=1):
            th = threading.Thread(target=other)
            th.start()
            th.join(timeout=10)
        assert not th.is_alive()
        events = {e[0]: e for e in rec.drain()}
        assert events["other"][3:] == (None, 2)
        stats = rec.phase_stats()["outer"]
        assert stats["self_s"] == stats["total_s"]


class TestTelemetry:
    def test_disabled_span_is_shared_noop(self):
        tel = Telemetry("n", enabled=False)
        assert tel.span("pull") is _NULL_SPAN
        assert tel.span("push") is _NULL_SPAN  # same object: zero allocation
        with tel.span("pull"):
            pass
        assert len(tel.recorder) == 0
        tel.observe_staleness(3)
        tel.count("x")
        assert tel.staleness_stats()["count"] == 0

    def test_enabled_span_is_a_profiler_annotation(self, tmp_path):
        import jax
        from jax.profiler import ProfileData

        on, off = Telemetry("n", enabled=True), Telemetry("n", enabled=False)
        for _ in range(7):
            on.end_round(aggregated=False)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with on.span("fed_step"):
                with on.span("push"):
                    pass
            with off.span("pull"):
                pass
        finally:
            jax.profiler.stop_trace()
        path, = tmp_path.rglob("*.xplane.pb")
        found = {ev.name: {k: v for k, v in ev.stats}
                 for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("repro.")}
        assert found == {"repro.fed_step": {"round": 7},
                         "repro.push": {"parent": "fed_step", "round": 7}}

    def test_env_gating(self, monkeypatch):
        monkeypatch.delenv("REPRO_OBS", raising=False)
        assert not env_enabled()
        assert Telemetry("n").enabled is False
        monkeypatch.setenv("REPRO_OBS", "1")
        assert env_enabled()
        assert Telemetry("n").enabled is True
        monkeypatch.setenv("REPRO_OBS", "off")
        assert not env_enabled()

    def test_staleness_distribution(self):
        tel = Telemetry("n", enabled=True)
        for v in [0, 1, 1, 2, 10]:
            tel.observe_staleness(v)
        stats = tel.staleness_stats()
        assert stats["count"] == 5
        assert stats["mean"] == pytest.approx(14 / 5)
        assert stats["max"] == 10
        assert stats["p50"] == 1
        assert stats["p90"] == 10

    def test_snapshot_advances_seq_and_carries_deltas(self):
        tel = Telemetry("n0", enabled=True)
        with tel.span("pull"):
            pass
        tel.end_round(aggregated=True)
        p0 = tel.snapshot({"bytes_written": 100, "decode_hits": 3,
                           "decode_misses": 1})
        assert p0["seq"] == 0 and tel.seq == 1
        assert p0["node_id"] == "n0"
        assert p0["rounds"] == 1 and p0["aggregations"] == 1
        assert p0["phases"]["pull"]["count"] == 1
        assert p0["prefetch_hit_rate"] == pytest.approx(0.75)
        assert len(p0["spans"]) == 1
        name, ts_us, dur_us, parent, round_ = p0["spans"][0]
        assert name == "pull" and isinstance(ts_us, int) and dur_us >= 0
        assert parent is None and round_ == 0
        # wall-anchored: within a minute of now
        assert abs(ts_us / 1e6 - time.time()) < 60
        tel.end_round(aggregated=False)
        p1 = tel.snapshot({"bytes_written": 300, "decode_hits": 3,
                           "decode_misses": 1})
        assert p1["seq"] == 1
        assert p1["transport_delta"]["bytes_written"] == 200
        assert p1["window"]["rounds"] == 1
        assert p1["spans"] == []  # drained by the previous snapshot

    def test_snapshot_is_json_serializable(self):
        tel = Telemetry("n0", enabled=True)
        with tel.span("push"):
            pass
        tel.observe_staleness(2)
        with tel.span("train"):
            pass
        tel.end_round(aggregated=True)
        payload = tel.snapshot({"bytes_written": 10})
        json.dumps(payload)  # must not raise


# --------------------------------------------------------------------------
# obs blob family + hygiene
# --------------------------------------------------------------------------


class TestObsBlobs:
    def test_round_trip(self):
        blob = serialize_obs_blob("node-a", 7, {"rounds": 3, "x": 1.5})
        node, seq, payload = deserialize_obs_blob(blob)
        assert (node, seq) == ("node-a", 7)
        assert payload == {"rounds": 3, "x": 1.5}

    def test_non_obs_blob_raises(self):
        from repro.core import serialize_update, NodeUpdate
        blob = serialize_update(NodeUpdate(
            params=_params(), num_examples=1, node_id="n", counter=0,
            timestamp=0.0))
        with pytest.raises(ValueError):
            deserialize_obs_blob(blob)

    def test_excluded_from_flat_state_hash(self):
        store = WeightStore(InMemoryFolder())
        store.push(_nu("a", 0))
        h0 = store.state_hash()
        h0x = store.state_hash(exclude_node="b")
        store.push_obs("a", 0, {"rounds": 1})
        assert store.state_hash() == h0
        assert store.state_hash(exclude_node="b") == h0x
        assert store.pull_obs("a")[0][2] == {"rounds": 1}

    def test_excluded_from_sharded_state_hash(self):
        folders = ShardedFolders.from_folders(
            [InMemoryFolder() for _ in range(4)])
        store = ShardedWeightStore(folders)
        store.push(_nu("a", 0))
        store.push(_nu("b", 0))
        h0 = store.state_hash()
        h0x = store.state_hash(exclude_node="b")
        store.push_obs("a", 0, {"rounds": 1})
        store.push_obs("b", 0, {"rounds": 2})
        assert store.state_hash() == h0
        assert store.state_hash(exclude_node="b") == h0x
        assert len(store.pull_obs()) == 2
        assert store.pull_obs("b")[0][0] == "b"

    def test_survives_keep_history_false_gc(self):
        # delta transport GCs superseded bases/chains aggressively (including
        # the first-rebase leftover sweep); obs/ deposits must survive it
        store = WeightStore(InMemoryFolder(), transport="delta",
                            keep_history=False)
        store.push_obs("a", 0, {"rounds": 0})
        for c in range(6):  # rebase_every default triggers full rebases
            store.push(_nu("a", c, seed=c))
        assert store.pull_obs("a")[0][1] == 0
        assert [k for k in store.folder.keys() if k.startswith("obs/")]

    def test_round_trips_through_cache_uri(self):
        store = WeightStore(CachingFolder(InMemoryFolder()))
        store.push_obs("n", 0, {"rounds": 5})
        assert store.pull_obs()[0] == ("n", 0, {"rounds": 5})

    def test_obs_gc_bounds_trail(self):
        store = WeightStore(InMemoryFolder())
        for seq in range(10):
            store.push_obs("n", seq, {"seq": seq}, keep=4)
        keys = sorted(k for k in store.folder.keys() if k.startswith("obs/"))
        assert keys == [f"obs/n/{s:06d}" for s in range(6, 10)]


def _nu(node_id, counter, seed=0):
    from repro.core import NodeUpdate
    return NodeUpdate(params=_params(seed=seed), num_examples=1,
                      node_id=node_id, counter=counter, timestamp=0.0)


# --------------------------------------------------------------------------
# node integration
# --------------------------------------------------------------------------


class TestNodeIntegration:
    def test_nodes_flush_obs_and_observe_staleness(self):
        folder = InMemoryFolder()
        tel = Telemetry(enabled=True, flush_every=1)
        a = AsyncFederatedNode(shared_folder=folder, node_id="a", telemetry=tel)
        b = AsyncFederatedNode(shared_folder=folder, node_id="b",
                               telemetry=True)
        for i in range(3):
            a.update_parameters(_params(seed=i), 1)
            b.update_parameters(_params(seed=i + 10), 1)
        payloads = a.store.pull_obs("a")
        assert len(payloads) == 3
        last = payloads[-1][2]
        assert last["rounds"] == 3
        assert {"push", "pull"} <= set(last["phases"])
        assert last["staleness"]["count"] >= 1
        assert tel.node_id == "a"  # node filled in the blank id
        # b's telemetry=True default cadence hasn't flushed yet
        assert a.store.pull_obs("b") == []

    @pytest.mark.parametrize("scheme", ["memory://", "shard1+memory://"])
    def test_store_from_connect_records_codec_spans(self, scheme):
        uri = f"{scheme}tel-{uuid.uuid4().hex}"
        tel = Telemetry(enabled=True)
        store = connect(uri)
        node = AsyncFederatedNode(store=store, node_id="a", telemetry=tel)
        assert store.telemetry is tel
        AsyncFederatedNode(store=connect(uri), node_id="b").update_parameters(
            _params(seed=1), 1)
        assert node.update_parameters(_params(), 1) is not None
        pairs = {(name, parent) for name, _t0, _dur, parent, _round in tel.recorder.drain()}
        assert {"encode", "put", "get", "decode", "aggregate"} <= {name for name, _ in pairs}
        assert ("encode", "push") in pairs
        # the flat store decodes as it pulls; the sharded one as its push
        # refreshes the group summary, and then pulls from its cache
        assert {parent for name, parent in pairs if name == "decode"} \
            == {"pull" if scheme == "memory://" else "gossip.l0.refresh"}
        # a store that carries another node's telemetry is left alone
        other = Telemetry(enabled=True)
        shared = connect(uri, telemetry=other)
        AsyncFederatedNode(store=shared, node_id="c", telemetry=True)
        assert shared.telemetry is other

    def test_callback_records_the_federation_step(self):
        import jax.numpy as jnp

        from repro.optim import get_optimizer
        from repro.training import Trainer

        uri = f"memory://tel-cb-{uuid.uuid4().hex}"
        AsyncFederatedNode(store=connect(uri), node_id="peer").update_parameters(
            {"w": np.ones(4, np.float32)}, 1)
        tel = Telemetry(enabled=True)
        node = AsyncFederatedNode(store=connect(uri), node_id="a", telemetry=tel)
        trainer = Trainer(loss_fn=lambda p, batch, rng: (jnp.sum(p["w"] ** 2), {}),
                          optimizer=get_optimizer("sgd", 0.1),
                          init_params={"w": jnp.zeros(4, jnp.float32)})
        cb = FederatedCallback(node, num_examples_per_epoch=1)
        trainer.fit([None], epochs=1, callbacks=[cb])
        events = tel.recorder.drain()
        children = {name for name, _t0, _dur, parent, _round in events
                    if parent == "fed_step"}
        assert children == {"host_params", "push", "pull", "aggregate", "set_params"}
        (fed_round,) = {r for name, *_, r in events if name == "fed_step"}
        assert {r for *_, r in events} == {fed_round}
        stats = tel.recorder.phase_stats()
        covered = sum(stats[c]["total_s"] for c in children)
        assert stats["fed_step"]["self_s"] == pytest.approx(
            stats["fed_step"]["total_s"] - covered, abs=1e-9)

    def test_default_is_off_and_costs_nothing(self, monkeypatch):
        monkeypatch.delenv("REPRO_OBS", raising=False)
        node = AsyncFederatedNode(shared_folder=InMemoryFolder(), node_id="n")
        assert node.telemetry.enabled is False
        node.update_parameters(_params(), 1)
        assert [k for k in node.store.folder.keys()
                if k.startswith("obs/")] == []

    def test_sharded_node_flushes_to_home_group(self):
        folders = ShardedFolders.from_folders(
            [InMemoryFolder() for _ in range(2)])
        node = AsyncFederatedNode(
            shared_folder=folders, node_id="n0",
            telemetry=Telemetry(enabled=True, flush_every=1))
        node.update_parameters(_params(), 1)
        assert len(node.store.pull_obs("n0")) == 1

    def test_obs_flush_failure_never_breaks_federation(self, monkeypatch):
        node = AsyncFederatedNode(
            shared_folder=InMemoryFolder(), node_id="n",
            telemetry=Telemetry(enabled=True, flush_every=1))
        monkeypatch.setattr(node.store, "push_obs",
                            lambda *a, **k: 1 / 0)
        assert node.update_parameters(_params(), 1) is None  # no peers; no raise
        assert node.counter == 1


# --------------------------------------------------------------------------
# PipelineStats thread-safety (the satellite regression)
# --------------------------------------------------------------------------


class TestPipelineStatsThreadSafety:
    def test_concurrent_incr_loses_nothing(self):
        # Bare `+=` on an instance attribute is load/add/store in CPython —
        # with a tiny switch interval, racing threads routinely lose updates.
        # The locked incr() must be exact.
        stats = PipelineStats()
        threads, per_thread = 8, 2000
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work():
                for _ in range(per_thread):
                    stats.incr("bytes_written")
                    stats.incr("bytes_read", 3)
            ts = [threading.Thread(target=work) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            sys.setswitchinterval(old)
        assert stats.bytes_written == threads * per_thread
        assert stats.bytes_read == 3 * threads * per_thread

    def test_record_max_and_set_value(self):
        stats = PipelineStats()
        stats.record_max("max_chain_depth", 3)
        stats.record_max("max_chain_depth", 1)
        assert stats.max_chain_depth == 3
        stats.set_value("chain_depth", 2)
        assert stats.chain_depth == 2

    def test_reset_preserves_lock_identity(self):
        stats = PipelineStats()
        lock = stats._lock
        stats.incr("encodes")
        stats.reset()
        assert stats.encodes == 0
        assert stats._lock is lock  # a swapped lock would orphan waiters

    def test_as_dict_snapshot(self):
        stats = PipelineStats()
        stats.incr("decodes", 5)
        d = stats.as_dict()
        assert d["decodes"] == 5 and "residual_norm" in d


# --------------------------------------------------------------------------
# bounded callback history
# --------------------------------------------------------------------------


class TestHistoryCap:
    class _StubStore:
        def stop_prefetch(self):
            pass

    class _StubNode:
        def __init__(self):
            self.store = TestHistoryCap._StubStore()
            self.telemetry = Telemetry(enabled=False)

        def update_parameters(self, params, num_examples, metrics=None):
            return None

    class _StubTrainer:
        def host_params(self):
            return {}

    def test_history_is_bounded(self):
        cb = FederatedCallback(self._StubNode(), num_examples_per_epoch=1,
                               history_limit=5)
        trainer = self._StubTrainer()
        for epoch in range(50):
            cb.on_epoch_end(trainer, epoch, {})
        assert len(cb.history) == 5
        assert [h["epoch"] for h in cb.history] == list(range(45, 50))

    def test_default_cap_exists(self):
        cb = FederatedCallback(self._StubNode(), num_examples_per_epoch=1)
        assert cb.history.maxlen == 10_000


# --------------------------------------------------------------------------
# Chrome trace export
# --------------------------------------------------------------------------


def assert_valid_chrome_trace(doc):
    """Minimal Chrome trace-event format check (the JSON object form)."""
    assert isinstance(doc, dict)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    for e in events:
        assert isinstance(e["name"], str)
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], int) and e["ts"] >= 0
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        else:
            assert e["name"] == "process_name"
            assert isinstance(e["args"]["name"], str)
    json.dumps(doc)


class TestTraceExport:
    def test_chrome_trace_schema(self):
        tel = Telemetry("n0", enabled=True)
        for phase in ("pull", "aggregate", "push"):
            with tel.span(phase):
                pass
        payload = tel.snapshot()
        doc = chrome_trace({"n0": [payload]})
        assert_valid_chrome_trace(doc)
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in spans} == {"pull", "aggregate", "push"}

    def test_nodes_become_processes(self):
        t0 = Telemetry("a", enabled=True)
        t1 = Telemetry("b", enabled=True)
        for tel in (t0, t1):
            with tel.span("pull"):
                pass
        doc = chrome_trace({"a": [t0.snapshot()], "b": [t1.snapshot()]})
        metas = {e["args"]["name"]: e["pid"]
                 for e in doc["traceEvents"] if e["ph"] == "M"}
        assert set(metas) == {"a", "b"}
        assert len(set(metas.values())) == 2


# --------------------------------------------------------------------------
# rollups + the 8-node soak acceptance
# --------------------------------------------------------------------------


class TestRollups:
    def test_rollups_from_synthetic_payloads(self):
        def payload(node, rounds, t, stale_mean):
            return {
                "node_id": node, "rounds": rounds, "aggregations": rounds,
                "time_unix": t,
                "phases": {"pull": {"count": rounds, "total_s": 0.01 * rounds,
                                    "mean_s": 0.01, "min_s": 0.01,
                                    "max_s": 0.01},
                           "train": {"count": rounds, "total_s": 0.2 * rounds,
                                     "mean_s": 0.2, "min_s": 0.2,
                                     "max_s": 0.2}},
                "staleness": {"count": rounds, "mean": stale_mean,
                              "p50": stale_mean, "p90": stale_mean,
                              "max": stale_mean},
                "transport": {"bytes_written": 100 * rounds},
                "window": {"rounds_per_sec": 1.0},
            }

        obs = {
            "a": [payload("a", 2, 100.0, 1.0), payload("a", 6, 102.0, 1.0)],
            "b": [payload("b", 4, 101.0, 3.0)],
        }
        roll = telemetry_rollups(obs)
        assert roll["fleet"]["nodes_reporting"] == 2
        # a: 4 rounds over 2s from first->last payload
        assert roll["nodes"]["a"]["rounds_per_sec"] == pytest.approx(2.0)
        assert roll["nodes"]["a"]["rounds"] == 6
        assert roll["fleet"]["staleness_mean"] == pytest.approx(2.0)
        assert roll["fleet"]["phase_ms"]["pull"] == pytest.approx(10.0)
        assert roll["nodes"]["b"]["phase_ms"]["train"] == pytest.approx(200.0)
        assert roll["fleet"]["bytes_written"] == 1000

    def test_empty_rollups(self):
        roll = telemetry_rollups({})
        assert roll["fleet"]["nodes_reporting"] == 0
        assert roll["nodes"] == {}


@pytest.mark.slow
def test_eight_node_soak_report_and_trace(tmp_path, capsys):
    """Acceptance: an 8-node soak's SoakReport carries per-node staleness +
    phase rollups assembled from obs/ blobs alone, and ``repro.obs trace``
    exports schema-valid Chrome trace JSON from the same store."""
    store = str(tmp_path / "soak")
    spec = FleetSpec(store_uri=store, name="obs-soak", num_nodes=8, rounds=3,
                     runner="thread", round_sleep=0.01, settle=0.2,
                     result_timeout=60)
    report = run_fleet_local(spec, num_workers=2)
    assert report.passed
    tel = report.telemetry
    assert tel["fleet"]["nodes_reporting"] == 8
    for node_id in spec.node_ids():
        per = tel["nodes"][node_id]
        assert per["rounds"] >= spec.rounds
        assert "staleness_mean" in per and "staleness_p90" in per
        assert {"pull", "push"} <= set(per["phase_ms"])
    assert "telemetry: 8/8 nodes" in report.summary()
    # the dashboard renders from blobs alone
    import repro.obs as obs_cli
    assert obs_cli.main(["watch", "--store", store, "--once"]) == 0
    out = capsys.readouterr().out
    assert "8 nodes reporting" in out
    # and the trace exporter emits valid Chrome trace JSON
    trace_path = str(tmp_path / "trace.json")
    assert obs_cli.main(["trace", "--store", store, "--out", trace_path]) == 0
    doc = json.load(open(trace_path))
    assert_valid_chrome_trace(doc)
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert len(pids) == 8


# --------------------------------------------------------------------------
# logging knob
# --------------------------------------------------------------------------


class TestLogs:
    def test_silent_by_default(self):
        from repro.logs import get_logger
        logger = get_logger("test")
        assert logger.name == "repro.test"
        root = logging.getLogger("repro")
        assert any(isinstance(h, logging.NullHandler)
                   for h in root.handlers) or not root.handlers

    def test_configure_and_teardown(self):
        import io
        from repro.logs import configure, get_logger
        stream = io.StringIO()
        configure("debug", stream=stream)
        try:
            get_logger("x").debug("hello from the test")
            assert "hello from the test" in stream.getvalue()
        finally:
            configure(None)
        stream2 = io.StringIO()
        configure("warning", stream=stream2)
        try:
            get_logger("x").debug("should not appear")
            assert stream2.getvalue() == ""
        finally:
            configure(None)

    def test_scoped_configure(self):
        import io
        from repro.logs import configure, get_logger
        stream = io.StringIO()
        configure("debug:fleet", stream=stream)
        try:
            get_logger("fleet").debug("fleet event")
            get_logger("store").debug("store event")
            text = stream.getvalue()
            assert "fleet event" in text
            assert "store event" not in text
        finally:
            configure(None)
