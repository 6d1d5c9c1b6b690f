"""Federated silo training: one ``AsyncFederatedNode`` + ``Trainer`` on the chip.

Set-up, in order:

1. the node's weights on the device from the seed (one jitted call), and the
   weights of K−1 peer silos beside them, copied to the host;
2. the data from the seed (``make_synthetic_wikitext`` at the vocabulary of
   the configuration);
3. the store: an in-memory folder from ``repro.api.connect``, with the
   traffic's transport (``program_parts``), that the peers fill. Each peer's update is encoded once, as the full transport encodes
   it; a thread puts it into the folder at the traffic's fixed period, the
   peers offset from each other by an equal share of it (class ``Peers``);
4. the node's first three steps, each through ``Trainer.fit`` on a fresh
   batch: they compile the step, and they are what the reference checks;
5. one federation step, which warms the push, pull, aggregate and
   ``set_params`` path.

The window then runs whole rounds back to back, each ``Trainer.fit`` over
``steps_per_round`` local steps with ``FederatedCallback`` federating at its
end, until ``seconds`` have passed. ``train_tokens_per_s`` is the tokens of
all those rounds over all their time.

``correct`` compares (see the checks below) the first three steps with the
float32 reference, and the parameters that the last round's federation step
placed back on the device with the weighted mean, in float64, of the updates
that step actually pushed and pulled.
"""
from __future__ import annotations

import gc
import itertools
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import costs, trace as tr
from bench.common import Check, memory_peak, metric, phases, phases_since
from bench.reference import lm as ref


def nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}."""
    out: dict = {}
    for path, v in flat.items():
        *parents, leaf = path.split("/")
        d = out
        for p in parents:
            d = d.setdefault(p, {})
        d[leaf] = v
    return out


def flatten(tree) -> dict:
    """The leaves of a tree of dicts by their "a/b/c" paths."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in leaves}


@jax.jit
def _diff_norms(a: dict, b: dict) -> dict:
    return {k: jnp.linalg.norm((a[k].astype(jnp.float32) - b[k].astype(jnp.float32)).ravel())
            for k in a}


def norm_gap(prog: dict, want: dict, *, keep=None) -> float:
    """Worst leaf of |‖prog‖ − ‖want‖|, over the larger of that leaf's
    ‖want‖ and the median leaf's."""
    med = float(np.median(list(want.values())))
    gaps = [abs(prog[k] - want[k]) / max(want[k], med, 1e-30)
            for k in want if keep is None or k in keep]
    return max(gaps) if gaps else float("nan")


def compare_steps(got: dict, want: dict) -> dict:
    """The numbers that decide a train cell's ``correct``, from the first
    steps of the program (or of a control) and of the reference:

    - ``loss_gap``: the largest relative gap of a step's loss;
    - ``grad_gap``: the first gradient's norm, by the worst leaf;
    - ``change_gap``: the norm of the parameters' change over the steps, by
      the worst leaf, leaving out the leaves whose reference gradient is
      under a thousandth of the median leaf's (they move by round-off)."""
    med = float(np.median(list(want["grad_norms"].values())))
    keep = {k for k, v in want["grad_norms"].items() if v >= 1e-3 * med}
    return {
        "loss_gap": max(abs(p - w) / abs(w) for p, w in zip(got["losses"], want["losses"])),
        "grad_gap": norm_gap(got["grad_norms"], want["grad_norms"]),
        "change_gap": norm_gap(got["change_norms"], want["change_norms"], keep=keep),
    }


def first_batches(tokens, traffic: dict, seed: int, n: int = 3) -> list[dict]:
    """The batches of the first ``n`` steps, as the driver's feed makes them."""
    from repro.data import lm_batch_iterator

    return [next(lm_batch_iterator(tokens, batch_size=traffic["batch"],
                                   seq_len=traffic["seq_len"], seed=seed, epoch=e))
            for e in range(n)]


def optimizer_of(traffic: dict) -> dict:
    """The traffic's AdamW hyperparameters, as the reference takes them."""
    hp = traffic["optimizer"]
    return dict(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], wd=hp["weight_decay"])


def program_parts(traffic: dict):
    """The strategy, transport and optimizer that the traffic names, built by
    the program's own constructors.

    The checks of ``correct`` know the example-weighted mean, AdamW, and the
    full transport's wire format (the peers encode their updates once, as it
    does); the folder is in memory (``store``: a folder on disk would write
    every peer deposit, gigabytes a round in the granite cell). Any other value
    raises, rather than run work that the checks cannot judge."""
    from repro.core.strategies import get_strategy
    from repro.core.transport import normalize_transport
    from repro.optim import get_optimizer

    hp = dict(traffic["optimizer"])
    name = hp.pop("name")
    transport = normalize_transport(traffic["transport"])
    for key, got, known in (("store", traffic["store"], "memory"),
                            ("strategy", traffic["strategy"], "fedavg"),
                            ("transport", transport, normalize_transport("full")),
                            ("optimizer", name, "adamw")):
        if got != known:
            raise ValueError(f"traffic {key} {got!r}: this driver runs and checks {known!r} only")
    return get_strategy(traffic["strategy"]), transport, get_optimizer(name, hp.pop("lr"), **hp)


def encoded(tree, node_id: str, counter: int, num_examples: int) -> bytes:
    """An update as the store's full transport writes it to ``latest/<node>``."""
    from repro.core.serialize import NodeUpdate, serialize_update

    return serialize_update(NodeUpdate(params=tree, num_examples=num_examples,
                                       node_id=node_id, counter=counter,
                                       timestamp=time.time()))


class Peers:
    """K−1 peer silos, which stand for silos on other hosts: threads that put
    each peer's update, encoded once in set-up as the full transport encodes
    it, into the shared folder every ``period`` seconds (peer i offset by
    i/(K−1) of a period). Every put is a new version, which the node pulls
    and decodes as a fresh deposit; the threads do no other work in the
    node's process."""

    def __init__(self, uri: str, trees: list, num_examples: int, period: float):
        from repro.api import connect

        self.folder = connect(uri).folder
        self.blobs = [encoded(t, f"peer{i}", 0, num_examples) for i, t in enumerate(trees)]
        self.period = float(period)
        self.deposits = [0] * len(trees)
        self.errors: list[BaseException] = []
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def start(self) -> "Peers":
        t0 = time.monotonic()
        for i in range(len(self.blobs)):
            th = threading.Thread(target=self._run, args=(i, t0), name=f"peer{i}", daemon=True)
            th.start()
            self._threads.append(th)
        return self

    def _run(self, i: int, t0: float) -> None:
        try:
            due = t0 + self.period * i / len(self.blobs)
            while not self._stop.wait(max(0.0, due - time.monotonic())):
                self.folder.put(f"latest/peer{i}", self.blobs[i])
                self.deposits[i] += 1
                due += self.period
        except BaseException as e:  # noqa: BLE001 - reported by the driver
            self.errors.append(e)

    def wait_filled(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while min(self.deposits) == 0 and not self.errors:
            if time.monotonic() > deadline:
                raise TimeoutError("the peers did not fill the store")
            time.sleep(0.01)

    def stop(self) -> None:
        self._stop.set()
        for th in self._threads:
            th.join()
        if self.errors:
            raise RuntimeError(f"a peer failed: {self.errors[0]!r}")


class Recorder:
    """What the node's federation step pushed, pulled and placed back: the
    benchmark's span around ``FederatedCallback.on_epoch_end`` and, for the
    last step, its inputs by reference."""

    def __init__(self, node, trainer):
        self.fed_spans: list[float] = []
        self.pull_spans: list[float] = []
        self.update_spans: list[float] = []
        self.last: dict = {}
        self._cur: dict = {}
        pull, update = node.store.pull, node.update_parameters

        def recording_pull(*a, **kw):
            t0 = time.perf_counter()
            peers = pull(*a, **kw)
            self.pull_spans.append(time.perf_counter() - t0)
            self._cur["peers"] = peers
            return peers

        def recording_update(params, **kw):
            self._cur = {"own": params, "own_examples": kw["num_examples"], "peers": []}
            t0 = time.perf_counter()
            out = update(params, **kw)
            self.update_spans.append(time.perf_counter() - t0)
            self._cur["aggregated"] = out is not None
            return out

        node.store.pull = recording_pull
        node.update_parameters = recording_update
        self.trainer = trainer

    def wrap(self, callback):
        outer = self

        class Timed(type(callback)):
            def on_epoch_end(self, trainer, epoch, logs):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.fed_step"):
                    super().on_epoch_end(trainer, epoch, logs)
                outer.fed_spans.append(time.perf_counter() - t0)
                outer.last = outer._cur

        callback.__class__ = Timed
        return callback


def aggregation_gap(spec_paths, own: dict, peers, own_examples: int, placed: dict) -> float:
    """Worst leaf of ‖placed − bf16(mean)‖ / ‖mean‖, where ``mean`` is the
    float64 example-weighted mean of own and the pulled peers' updates."""
    weights = np.asarray([own_examples] + [u.num_examples for u in peers], np.float64)
    weights = np.maximum(weights, 1.0)
    weights /= weights.sum()
    worst = 0.0
    for path in spec_paths:
        parts = [np.asarray(own[path], np.float64)]
        for u in peers:
            i = u.spec.index[path]
            o, n = int(u.spec.offsets[i]), int(u.spec.sizes[i])
            parts.append(np.asarray(u.flat[o:o + n], np.float64).reshape(parts[0].shape))
        mean = sum(w * x for w, x in zip(weights, parts))
        want = ref.bf16_round(mean.astype(np.float32))
        got = np.asarray(placed[path], np.float32)
        denom = max(float(np.linalg.norm(mean)), 1e-30)
        worst = max(worst, float(np.linalg.norm(got - want)) / denom)
    return worst


def run(cell, *, seed: int, seconds: float, trace_dir: str | None, t_start: float,
        clock) -> dict:
    from repro.api import connect
    from repro.configs import get_config
    from repro.core import AsyncFederatedNode, FederatedCallback
    from repro.core.telemetry import Telemetry
    from repro.data import lm_batch_iterator, make_synthetic_wikitext
    from repro.models import build_model
    from repro.training import Trainer

    tf, cf = cell.traffic, cell.config
    arch = ref.Arch(cf)
    mcfg = get_config(cf["arch"]).replace(**cf.get("overrides", {}))
    model = build_model(mcfg)
    layout = {k: (tuple(v.shape), jnp.dtype(v.dtype)) for k, v in
              flatten(jax.eval_shape(model.init, jax.random.PRNGKey(0))).items()}
    want = {k: (tuple(s), arch.dtype) for k, s in arch.param_shapes().items()}
    if layout != want:
        raise RuntimeError(f"the program's parameters differ from the configuration's: "
                           f"{sorted(set(layout.items()) ^ set(want.items()))[:4]}")

    B, S, steps, K = tf["batch"], tf["seq_len"], tf["steps_per_round"], tf["silos"]
    strategy, transport, optimizer = program_parts(tf)
    opt = optimizer_of(tf)

    # 1. weights: the node's on the device, the peers' on the host
    peer_trees = [nest(jax.device_get(ref.make_weights(arch, seed, salt=i + 1)))
                  for i in range(K - 1)]
    params = nest(ref.make_weights(arch, seed))
    # 2. data
    tokens = make_synthetic_wikitext(vocab_size=arch.vocab, seed=seed).train_tokens
    epochs = itertools.count()

    def feed(_epoch=None):
        """Fresh random windows of the stream on every call."""
        return lm_batch_iterator(tokens, batch_size=B, seq_len=S, seed=seed, epoch=next(epochs))

    # 3. the store, filled by the peers
    uri = f"memory://bench-train-{os.getpid()}"
    tel = Telemetry(enabled=True) if trace_dir else False
    node = AsyncFederatedNode(strategy=strategy, store=connect(uri, transport=transport),
                              node_id="silo0", telemetry=tel)
    trainer = Trainer(loss_fn=lambda p, batch, rng: model.loss(p, batch), optimizer=optimizer,
                      init_params=params, seed=seed % 2**31, name="silo0")
    del params
    examples = steps * B
    peers = Peers(uri, peer_trees, examples, tf["peer_period_s"]).start()
    del peer_trees
    rec = Recorder(node, trainer)
    cb = rec.wrap(FederatedCallback(node, num_examples_per_epoch=examples))
    try:
        peers.wait_filled(timeout=120.0)
        # 4. the first three steps, through the window's own call and feed
        batches, prog_losses = [], []
        for i in range(3):
            batch = next(feed())
            batches.append(batch)
            trainer.fit([batch], epochs=1, steps_per_epoch=1)
            prog_losses.append(float(trainer.log[-1]["loss"]))
            if i == 0:  # Adam's first moment after one step is (1 − b1)·g
                grad = {k: v / (1.0 - opt["b1"])
                        for k, v in ref.leaf_norms(flatten(trainer.opt_state["m"])).items()}
        start = ref.make_weights(arch, seed)
        change = {k: float(v) for k, v in
                  jax.device_get(_diff_norms(flatten(trainer.params), start)).items()}
        del start
        # 5. one federation step
        cb.on_epoch_end(trainer, 0, dict(trainer.log[-1]))
        jax.block_until_ready(trainer.params)
        for spans in (rec.fed_spans, rec.pull_spans, rec.update_spans):
            spans.clear()
        phases0 = phases(tel)
        setup_s = time.perf_counter() - t_start

        # the window: whole rounds until `seconds` have passed and the last
        # round aggregated (its federation step is the one checked), at most
        # three rounds more
        rounds, cpu = [], [os.times()[:2]]
        compiles0 = clock.read()[1]
        tracer = tr.Tracer(trace_dir, tf["trace_seconds"])
        t0 = time.perf_counter()
        tracer.start(t0)
        while True:
            with jax.profiler.TraceAnnotation("bench.round"):
                trainer.fit(feed, epochs=1, steps_per_epoch=steps, callbacks=[cb])
            rounds.append(time.perf_counter())
            cpu.append(os.times()[:2])
            tracer.poll(rounds[-1])
            late = rounds[-1] - t0 >= seconds
            if late and (rec.last.get("aggregated") or rounds[-1] - t0 >= seconds * 1.5):
                break
        tracer.close()
        window_compiles = clock.read()[1] - compiles0
    finally:
        peers.stop()
    elapsed = rounds[-1] - t0
    n_tokens = len(rounds) * steps * B * S
    aggregated = node.num_aggregations - 1  # the warm-up step aggregated once
    window_phases = {**phases_since(tel, phases0),
                     "fed_step": [len(rec.fed_spans), sum(rec.fed_spans)]}
    peak = memory_peak()
    # where each round's time went, on the host's clock
    round_notes = {"round_s": np.diff([t0] + rounds).tolist(), "fed_s": rec.fed_spans,
                   "update_s": rec.update_spans, "pull_s": rec.pull_spans,
                   "user_s": np.diff(np.asarray(cpu)[:, 0]).tolist(),
                   "sys_s": np.diff(np.asarray(cpu)[:, 1]).tolist()}

    t_check = time.perf_counter()
    # correct: the last federation step against the float64 weighted mean
    last = rec.last
    if last.get("aggregated"):
        own = flatten(last["own"])
        placed = {k: np.asarray(v, np.float32) for k, v in
                  jax.device_get(flatten(trainer.params)).items()}
        agg_gap = aggregation_gap(list(own), own, last["peers"], last["own_examples"], placed)
    else:
        agg_gap = float("inf")
    del trainer, node, cb, rec, last
    gc.collect()

    # correct: the first three steps against the float32 reference
    want_steps = ref.train_reference(arch, jax.device_get(ref.make_weights(arch, seed)),
                                     batches, opt)
    got = {"losses": prog_losses, "grad_norms": grad, "change_norms": change}
    numbers = {**compare_steps(got, want_steps), "agg_gap": agg_gap}
    checks = [Check(k, v, cell.limits[k]) for k, v in numbers.items()]
    return {
        "e2e": {"train_tokens_per_s": metric(n_tokens / elapsed, "tokens/s")},
        "setup_s": setup_s,
        "attempted": len(rounds),
        "failed": len(rounds) - aggregated,
        "checks": checks,
        "memory_peak_bytes": peak,
        "record": {
            "kind": "train",
            "rounds": len(rounds),
            "steps": len(rounds) * steps,
            "window_s": elapsed,
            "phases": window_phases,
            "flops_per_step": costs.train_flops(arch, B, S),
        },
        "notes": {"rounds": len(rounds), "aggregations": aggregated,
                  "check_s": time.perf_counter() - t_check,
                  "window_s": elapsed, "window_compiles": window_compiles,
                  **round_notes,
                  "peer_deposits": list(peers.deposits), "reference_losses":
                  want_steps["losses"], "program_losses": prog_losses},
    }
