"""Cross-process federation: the serverless claim with real OS processes.

Every client here is a separate interpreter (spawn start method) sharing
nothing but a DiskFolder directory — the honest version of the paper's "any
remote folder suffices" claim. Child targets must be module-level functions
(spawn pickles them by qualified name).
"""
import os
import signal
import time

import numpy as np
import pytest

from repro.core import (
    AsyncFederatedNode,
    DiskFolder,
    NodeUpdate,
    ProcessCrashed,
    ShardedWeightStore,
    WeightStore,
    run_multiprocess,
)
from repro.core.gossip import GROUP_PEER_PREFIX
from repro.core.strategies import FedAvg

pytestmark = pytest.mark.multiprocess


# --- child targets (module-level: picklable under spawn) --------------------


def _returns_value(x):
    return x * 2


def _raises():
    raise ValueError("injected failure")


def _hangs_forever():
    while True:
        time.sleep(0.1)


def _atomic_writer(directory, blob_a, blob_b, iterations):
    folder = DiskFolder(directory)
    for i in range(iterations):
        folder.put("latest/w", blob_a if i % 2 == 0 else blob_b)
    folder.put("done", b"x")


def _push_update(directory, node_id, value, counter):
    store = WeightStore(DiskFolder(directory))
    store.push(NodeUpdate({"w": np.full((8,), float(value), np.float32)},
                          num_examples=3, node_id=node_id, counter=counter))
    return store.state_hash()


def _pull_update(directory, node_id):
    update = WeightStore(DiskFolder(directory)).pull_node(node_id)
    assert update is not None
    return {"value": float(update.params["w"][0]), "counter": update.counter,
            "num_examples": update.num_examples}


def _fed_client(directory, node_id, target, *, epochs, peers_required,
                die_after_pushes=None, max_wait=60.0):
    """Quadratic consensus client: local step pulls toward own target, the
    async federation step mixes in whatever peers have deposited.

    ``die_after_pushes`` turns the client into a crash victim: after that many
    federation pushes it hangs so the harness's SIGKILL lands mid-training.
    Survivors keep looping (past their nominal epoch count if necessary) until
    they have aggregated ``peers_required`` distinct peers, so the "survivors
    saw the dead node's deposit" assertion is deterministic, not timing luck.
    """
    node = AsyncFederatedNode(strategy=FedAvg(), shared_folder=DiskFolder(directory),
                              node_id=node_id)
    w = np.zeros((4,), np.float32)
    seen_peers: set = set()
    deadline = time.monotonic() + max_wait
    epoch = 0
    while epoch < epochs or (len(seen_peers) < peers_required and time.monotonic() < deadline):
        w = w + 0.3 * (np.float32(target) - w)  # local "training"
        aggregated = node.update_parameters({"w": w}, num_examples=5)
        seen_peers.update(u.node_id for u in node.store.pull(exclude=node_id))
        if aggregated is not None:
            w = aggregated["w"]
        if die_after_pushes is not None and node.num_pushes >= die_after_pushes:
            while True:  # "mid-training": park here until SIGKILL arrives
                time.sleep(0.05)
        time.sleep(0.05)
        epoch += 1
    return {
        "final": w.tolist(),
        "epochs": epoch,
        "pushes": node.num_pushes,
        "aggregations": node.num_aggregations,
        "seen_peers": sorted(seen_peers),
    }


def _resumable_client(directory, node_id, epochs, die_after_pushes=None):
    """Crash-and-restart client: reports whether it bootstrapped from its own
    latest/ blob and where its counter started. ``die_after_pushes`` parks the
    client mid-training so the harness SIGKILL lands deterministically."""
    node = AsyncFederatedNode(strategy=FedAvg(), shared_folder=DiskFolder(directory),
                              node_id=node_id)
    start_counter = node.counter
    resumed_from = None if node.resumed is None else float(node.resumed.params["w"][0])
    w = (np.asarray(node.resumed.params["w"]) if node.resumed is not None
         else np.zeros((4,), np.float32))
    for _ in range(epochs):
        w = w + np.float32(1.0)  # local "training": counts total progress
        aggregated = node.update_parameters({"w": w}, num_examples=5)
        if aggregated is not None:
            w = aggregated["w"]
        if die_after_pushes is not None and node.num_pushes >= die_after_pushes:
            while True:  # park mid-training until the SIGKILL arrives
                time.sleep(0.05)
    return {"start_counter": start_counter, "resumed_from": resumed_from,
            "final_counter": node.counter, "w0": float(w[0])}


def _sharded_fed_client(directory, node_id, group_map, num_groups, target, *,
                        epochs, max_wait=60.0):
    """Quadratic consensus client over a sharded gossip store: same contract
    as ``_fed_client`` but each process scans only its home group's folder;
    cross-group information arrives as ``group:<g>`` pseudo-peers."""
    store = ShardedWeightStore(f"shard{num_groups}+{directory}", group_of=group_map)
    node = AsyncFederatedNode(strategy=FedAvg(), store=store, node_id=node_id)
    w = np.zeros((4,), np.float32)
    seen: set = set()
    deadline = time.monotonic() + max_wait
    epoch = 0
    while epoch < epochs or (
        not any(p.startswith(GROUP_PEER_PREFIX) for p in seen)
        and time.monotonic() < deadline
    ):
        w = w + 0.3 * (np.float32(target) - w)
        aggregated = node.update_parameters({"w": w}, num_examples=5)
        seen.update(u.node_id for u in store.pull(exclude=node_id))
        if aggregated is not None:
            w = aggregated["w"]
        time.sleep(0.05)
        epoch += 1
    return {"final": w.tolist(), "pushes": node.num_pushes,
            "aggregations": node.num_aggregations, "seen_peers": sorted(seen)}


# --- harness contract -------------------------------------------------------


def test_run_multiprocess_collects_results_and_errors():
    res = run_multiprocess([(_returns_value, (21,)), _raises], names=["ok", "bad"])
    assert res[0].error is None and res[0].result == 42 and res[0].exitcode == 0
    assert isinstance(res[1].error, ProcessCrashed)
    assert "injected failure" in res[1].traceback


def test_run_multiprocess_sigkill_injection():
    t0 = time.monotonic()
    res = run_multiprocess([_hangs_forever], kill_after={0: 0.5}, join_timeout=30.0)
    assert isinstance(res[0].error, ProcessCrashed)
    assert res[0].exitcode == -signal.SIGKILL
    assert time.monotonic() - t0 < 25.0  # did not wait out the join timeout


# --- DiskFolder cross-process semantics -------------------------------------


def test_diskfolder_atomic_put_under_concurrent_reader(tmp_path):
    """Readers racing a writer in another process never observe a torn blob."""
    blob_a, blob_b = b"A" * 4096, b"B" * 8192
    folder = DiskFolder(str(tmp_path))
    res_holder = {}

    def read_loop():
        torn = 0
        reads = 0
        reader = DiskFolder(str(tmp_path))
        while reader.get("done") is None:
            blob = reader.get("latest/w")
            if blob is not None:
                reads += 1
                if blob != blob_a and blob != blob_b:
                    torn += 1
        res_holder["torn"], res_holder["reads"] = torn, reads

    import threading

    reader_thread = threading.Thread(target=read_loop, daemon=True)
    reader_thread.start()
    res = run_multiprocess([(_atomic_writer, (str(tmp_path), blob_a, blob_b, 200))])
    assert res[0].error is None
    reader_thread.join(timeout=30)
    assert not reader_thread.is_alive()
    assert res_holder["torn"] == 0
    assert res_holder["reads"] > 0
    assert folder.get("latest/w") in (blob_a, blob_b)


def test_diskfolder_state_hash_detects_cross_process_writes(tmp_path):
    folder = DiskFolder(str(tmp_path))
    h0 = folder.state_hash()
    res = run_multiprocess([(_push_update, (str(tmp_path), "remote", 1.0, 0))])
    assert res[0].error is None
    h1 = folder.state_hash()
    assert h0 != h1
    # the child and the parent compute identical hashes over identical state
    assert res[0].result == WeightStore(folder).state_hash()


def test_two_process_push_pull_roundtrip(tmp_path):
    res = run_multiprocess([
        (_push_update, (str(tmp_path), "writer", 7.5, 3)),
        (_pull_update, (str(tmp_path), "writer")),
    ])
    # NB: the pull client polls nothing — it may race the writer, so order the
    # processes: run writer first, then reader, each in its own interpreter.
    if res[1].error is not None:  # reader beat the writer: rerun reader alone
        res[1] = run_multiprocess([(_pull_update, (str(tmp_path), "writer"))])[0]
    assert res[0].error is None and res[1].error is None
    assert res[1].result == {"value": 7.5, "counter": 3, "num_examples": 3}


# --- the paper's robustness claim, across real processes ---------------------


def test_three_process_federation_survives_sigkill(tmp_path):
    """≥3 OS processes federate over a DiskFolder; one is SIGKILLed
    mid-training; the survivors finish and converge (async mode)."""
    targets = {"n0": 0.0, "n1": 1.0, "n2": 2.0}
    clients = [
        (_fed_client, (str(tmp_path), "n0", targets["n0"]),
         dict(epochs=10, peers_required=2)),
        (_fed_client, (str(tmp_path), "n1", targets["n1"]),
         dict(epochs=10, peers_required=2)),
        (_fed_client, (str(tmp_path), "n2", targets["n2"]),
         dict(epochs=10, peers_required=1, die_after_pushes=2)),
    ]
    res = run_multiprocess(clients, names=["n0", "n1", "n2"],
                           kill_after={2: 10.0}, join_timeout=120.0)

    # the victim died by SIGKILL, not by exception
    assert isinstance(res[2].error, ProcessCrashed)
    assert res[2].exitcode == -signal.SIGKILL

    # the survivors finished all their epochs, unblocked
    for r in res[:2]:
        assert r.error is None, r.traceback
        assert r.exitcode == 0
        assert r.result["epochs"] >= 10
        assert r.result["aggregations"] >= 1

    # both survivors aggregated the dead node's deposit at some point
    assert "n2" in res[0].result["seen_peers"]
    assert "n2" in res[1].result["seen_peers"]

    # convergence: survivors agree with each other (consensus), and sit inside
    # the convex hull of the targets rather than at their own target
    w0 = np.asarray(res[0].result["final"])
    w1 = np.asarray(res[1].result["final"])
    assert np.max(np.abs(w0 - w1)) < 1.0
    for w, own in ((w0, 0.0), (w1, 1.0)):
        assert w.min() >= -0.1 and w.max() <= 2.1
    assert np.max(np.abs(w0)) > 0.05  # n0 was pulled off its own target (0.0)


# --- restart/recovery: a SIGKILL'd client resumes, not restarts --------------


def test_sigkilled_client_resumes_from_own_blob(tmp_path):
    """Crash injection + restart: the reborn process (same node_id) bootstraps
    counter and params from its own latest/ deposit instead of starting over."""
    first = run_multiprocess(
        [(_resumable_client, (str(tmp_path), "phoenix", 50),
          {"die_after_pushes": 3})],
        kill_after={0: 10.0}, join_timeout=60.0)
    assert isinstance(first[0].error, ProcessCrashed)
    assert first[0].exitcode == -signal.SIGKILL

    reborn = run_multiprocess(
        [(_resumable_client, (str(tmp_path), "phoenix", 2))], join_timeout=60.0)
    assert reborn[0].error is None, reborn[0].traceback
    r = reborn[0].result
    # the victim deposited counters 0,1,2 before the kill → resume at 3
    assert r["start_counter"] == 3
    assert r["resumed_from"] is not None and r["resumed_from"] >= 3.0
    assert r["final_counter"] == 5  # progress continued, not restarted
    # training state carried over: w kept growing from the recovered value
    assert r["w0"] > r["resumed_from"]


def test_fresh_client_under_new_id_still_starts_at_zero(tmp_path):
    run_multiprocess([(_resumable_client, (str(tmp_path), "other", 2))],
                     join_timeout=60.0)
    res = run_multiprocess([(_resumable_client, (str(tmp_path), "newborn", 1))],
                           join_timeout=60.0)
    assert res[0].error is None, res[0].traceback
    assert res[0].result["start_counter"] == 0
    assert res[0].result["resumed_from"] is None


# --- sharded gossip store across real processes ------------------------------


def test_sharded_federation_across_processes(tmp_path):
    """4 OS processes, 2 groups, nothing shared but per-group disk folders:
    every client federates within its group and hears the other group via
    gossip summaries."""
    group_map = {"n0": 0, "n1": 0, "n2": 1, "n3": 1}
    targets = {"n0": 0.0, "n1": 1.0, "n2": 3.0, "n3": 4.0}
    clients = [
        (_sharded_fed_client, (str(tmp_path), nid, group_map, 2, targets[nid]),
         dict(epochs=10))
        for nid in group_map
    ]
    res = run_multiprocess(clients, names=list(group_map), join_timeout=120.0)
    for r in res:
        assert r.error is None, r.traceback
        assert r.result["aggregations"] >= 1
    by_id = {r.node_id: r.result for r in res}
    # every client eventually saw the OTHER group's summary pseudo-peer
    for nid, g in group_map.items():
        other = 1 - g
        assert f"{GROUP_PEER_PREFIX}{other}" in by_id[nid]["seen_peers"], by_id[nid]
    # cross-group mixing actually moved weights: each final sits strictly
    # inside the global target hull, not pinned at the group's own extreme
    for nid in group_map:
        w = np.asarray(by_id[nid]["final"])
        assert w.min() >= -0.2 and w.max() <= 4.2
    assert np.asarray(by_id["n0"]["final"]).max() > 0.3  # n0 pulled off target 0


def test_run_multiprocess_cancels_unfired_kill_timers():
    """A client that finishes before its scheduled kill must not leave the
    kill timer's thread behind: the supervisor cancels outstanding timers on
    normal join, so no thread — Timer or otherwise, daemon or not — survives
    the call."""
    import threading

    before = set(threading.enumerate())
    res = run_multiprocess([(_returns_value, (21,))], kill_after={0: 300.0})
    assert res[0].error is None and res[0].result == 42
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked, f"threads survived run_multiprocess: {leaked}"
    assert not any(isinstance(t, threading.Timer) for t in threading.enumerate())


def test_supervisor_restart_resumes_client(tmp_path):
    """The fleet worker's kill→respawn cycle at supervisor level: spawn a
    client, SIGKILL it, respawn under the same name — the second incarnation
    (same node id) resumes from the first one's deposits, and the first
    incarnation's result stays available as history."""
    from repro.core import ProcessSupervisor

    sup = ProcessSupervisor()
    try:
        sup.spawn("phoenix", _resumable_client, (str(tmp_path), "phoenix", 50),
                  {"die_after_pushes": 2})
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:  # wait for the park, then kill
            if WeightStore(DiskFolder(str(tmp_path))).pull_node("phoenix") is not None:
                break
            time.sleep(0.05)
        sup.kill("phoenix")
        sup.join(30.0)
        assert sup.result("phoenix").exitcode == -signal.SIGKILL
        assert isinstance(sup.result("phoenix").error, ProcessCrashed)

        # restart under the same name, this time without the parking kwargs
        # (exactly what the fleet worker does after an injected crash)
        sup.spawn("phoenix", _resumable_client, (str(tmp_path), "phoenix", 2))
        assert sup.incarnation("phoenix") == 1
        sup.join(60.0)
        reborn = sup.result("phoenix")
        assert reborn.error is None, reborn.traceback
        assert reborn.result["resumed_from"] is not None
        assert reborn.result["start_counter"] > 0
        # the killed incarnation's outcome is preserved as history
        assert isinstance(sup.history("phoenix")[0].error, ProcessCrashed)
    finally:
        sup.shutdown()


def test_run_multiprocess_rejects_bad_kill_index():
    with pytest.raises(ValueError):
        run_multiprocess([_returns_value], kill_after={5: 1.0})


def test_run_multiprocess_rejects_mismatched_names():
    with pytest.raises(ValueError):
        run_multiprocess([_returns_value, _returns_value], names=["only-one"])


def _sleeps_then_returns(delay, value):
    time.sleep(delay)
    return value


def test_supervisor_cancel_scheduled_kills_lets_client_finish(tmp_path):
    """The fleet worker's clean-finish path: an armed backstop SIGKILL can be
    disarmed without touching the process, so a victim that finishes cleanly
    before the timer fires completes normally — no crash, no -9 exitcode."""
    import threading

    from repro.core import ProcessSupervisor

    sup = ProcessSupervisor()
    try:
        sup.spawn("survivor", _sleeps_then_returns, (1.0, 7))
        sup.schedule_kill("survivor", 0.4)  # would land mid-sleep
        sup.cancel_scheduled_kills("survivor")
        sup.join(60.0)
        res = sup.result("survivor")
        assert res.error is None, res.traceback
        assert res.result == 7 and res.exitcode == 0
    finally:
        sup.shutdown()
    assert not any(isinstance(t, threading.Timer) and t.is_alive()
                   for t in threading.enumerate())


def _jax_platform():
    import jax

    return os.environ.get("JAX_PLATFORMS"), jax.config.jax_platforms, jax.default_backend()


def test_supervised_children_run_jax_on_the_host_cpu(monkeypatch):
    """On a TPU host a child that reached for the chip would hold it against
    its siblings; supervised children are pinned to the host CPU, and pass
    the pin on to the processes they start."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    res = run_multiprocess([_jax_platform, _jax_platform], join_timeout=120.0)
    for r in res:
        assert r.error is None, r.traceback
        assert r.result == ("cpu", "cpu", "cpu")
