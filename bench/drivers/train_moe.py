"""Federated silo training of an expert model (``deepseek_v3``): one
``AsyncFederatedNode`` + ``Trainer`` on the chip, as ``train`` runs a dense
model, with the differences an expert layer brings:

- the reference is ``reference.moe_lm`` (the expert layer run dense, one row
  at a time, Adam's moments on the host);
- the step takes the model's full rematerialisation when the traffic says
  ``remat``;
- the operations of a step count the routed rows the program reports
  (``train_flops``), not a fixed share of the experts;
- the record carries the program's routing counters (``moe_rows``,
  ``moe_rows_alloc``) and, in a traced run, the device seconds of the ops
  under the program's ``moe_route``/``moe_experts``/``moe_combine`` and
  ``mla`` scopes (``scope_seconds``, read through the compiled step's
  ``op_name`` metadata);
- the notes add ``route_gap``: the share of held-expert choices in the first
  batch, at the starting weights, on which the program's top-k and the
  reference's differ.

Set-up, the window and the checks of ``correct`` are ``train``'s (see its
docstring).
"""
from __future__ import annotations

import gc
import itertools
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import trace as tr
from bench.common import Check, memory_peak, metric, phases, phases_since
from bench.drivers.train import (Peers, Recorder, _diff_norms, aggregation_gap, compare_steps,
                                 flatten, nest, optimizer_of, program_parts)
from bench.reference import moe_lm as ref
from bench.reference.lm import leaf_norms

# the program's scopes, as they appear in an operation's name stack
SCOPES = {"moe": re.compile(r"(?<![\w])moe_(route|experts|combine)(?![\w])"),
          "mla": re.compile(r"(?<![\w])mla(?![\w])")}
EXPERT_LEAVES = ("moe/wi/w", "moe/wg/w", "moe/wo/w")


def train_flops(arch, batch: int, seq: int, routed_rows: float) -> float:
    """Forward and backward operations of one train step: 6 per token for
    every matmul weight outside the routed experts (the unembedding, the
    router and the shared experts included), 6·3·D·F for each token-choice
    routed to a held expert, and attention's two contractions over the whole
    S×S score matrix (QKᵀ at the query's width, PV at the value's)."""
    dense = sum(int(np.prod(s)) for p, s in arch.param_shapes().items()
                if not p.endswith(("/scale", "router_bias", "embed/table", *EXPERT_LEAVES))
                or p == "unembed/table")
    tokens = batch * seq
    attn = 6.0 * arch.layers * arch.heads * (arch.dn + arch.dr + arch.dv) * seq
    return (6.0 * dense + attn) * tokens + 6.0 * 3 * arch.d * arch.moe_ff * routed_rows


def route_gap(prog: np.ndarray, want: np.ndarray, first: int, held: int) -> float:
    """Of the held-expert choices (token, layer) of either side, the share
    that the other side did not make. prog, want: (layers, tokens, k)."""
    def held_sets(idx):
        local = idx - first
        return np.stack([np.any(local == e, axis=-1) for e in range(held)], axis=-1)
    a, b = held_sets(np.asarray(prog)), held_sets(np.asarray(want))
    total = int(a.sum() + b.sum())
    return float((a ^ b).sum() / total) if total else 0.0


def _hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name → ``op_name`` metadata, from a compiled module's text."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*op_name=\"([^\"]*)\"",
                         hlo_text, flags=re.M):
        out[m.group(1)] = m.group(2)
    return out


def scope_seconds(log_dir: str, layout: tr.Layout, hlo_text: str) -> dict:
    """Device seconds of the operations under each of ``SCOPES`` within the
    traced window: per scope, the union of the intervals of the operations
    whose name stack holds it. An operation's event names its instruction
    (the ``hlo_op`` stat, or the text before `` = ``); its name stack is that
    instruction's ``op_name`` in the compiled module ``hlo_text``. (The
    chip's operation events carry no name stack of their own.)"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(tr.newest_xplane(log_dir))
    planes = list(data.planes)
    windows = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
               for p in planes if p.name == layout.host_plane
               for line in p.lines for ev in line.events if ev.name == tr.WINDOW]
    if not windows:
        return {}
    lo, hi = windows[-1]
    names = _hlo_op_names(hlo_text)
    spans: dict[str, list] = {scope: [] for scope in SCOPES}
    for p in planes:
        if not p.name.startswith(layout.device_plane):
            continue
        for line in p.lines:
            if not line.name.startswith(layout.op_line):
                continue
            for ev in line.events:
                s, e = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if e <= lo or s >= hi:
                    continue
                inst = dict(ev.stats).get("hlo_op") or ev.name.lstrip("%").split(" = ")[0]
                op_name = names.get(inst, "")
                for scope, rx in SCOPES.items():
                    if rx.search(op_name):
                        spans[scope].append((s, e))
    return {scope: sum(e - s for s, e in tr.union(v, lo, hi)) for scope, v in spans.items()}


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
    remat = bool(tf.get("remat", False))
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
    uri = f"memory://bench-train-moe-{os.getpid()}"
    tel = Telemetry(enabled=True) if trace_dir else False
    node = AsyncFederatedNode(strategy=strategy, store=connect(uri, transport=transport),
                              node_id="silo0", telemetry=tel)
    trainer = Trainer(loss_fn=lambda p, batch, rng: model.loss(p, batch, remat=remat),
                      optimizer=optimizer, init_params=params, seed=seed % 2**31, name="silo0")
    del params
    examples = steps * B
    peers = Peers(uri, peer_trees, examples, tf["peer_period_s"]).start()
    del peer_trees
    rec = Recorder(node, trainer)
    cb = rec.wrap(FederatedCallback(node, num_examples_per_epoch=examples))
    try:
        peers.wait_filled(timeout=300.0)
        # 4. the first three steps, through the window's own call and feed
        batches, prog_losses = [], []
        for i in range(3):
            batch = next(feed())
            batches.append(batch)
            trainer.fit([batch], epochs=1, steps_per_epoch=1)
            prog_losses.append(float(trainer.log[-1]["loss"]))
            if i == 0:  # Adam's first moment after one step is (1 − b1)·g
                grad = {k: v / (1.0 - opt["b1"])
                        for k, v in leaf_norms(flatten(trainer.opt_state["m"])).items()}
        start = ref.make_weights(arch, seed)
        change = {k: float(v) for k, v in
                  jax.device_get(_diff_norms(flatten(trainer.params), start)).items()}
        prog_routes = np.asarray(jax.jit(model.routes)(nest(start), jnp.asarray(batches[0]["tokens"])))
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
    window_log = trainer.log[-len(rounds):]
    counters = {k: float(np.mean([e[k] for e in window_log]))
                for k in ("moe_rows", "moe_rows_alloc", "moe_load_max")}
    scopes = {}
    if trace_dir:  # the step as compiled (read back from the compile cache)
        hlo = trainer._train_step.lower(trainer.params, trainer.opt_state, batches[0],
                                        jax.random.PRNGKey(0)).compile().as_text()
        layout = tr.TPU if jax.devices()[0].platform == "tpu" else tr.cpu_layout()
        scopes = scope_seconds(trace_dir, layout, hlo)
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
        del own, placed
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
            "flops_per_step": train_flops(arch, B, S, counters["moe_rows"]),
            "moe_rows": counters["moe_rows"],
            "moe_rows_alloc": counters["moe_rows_alloc"],
            "scope_seconds": scopes,
        },
        "notes": {"rounds": len(rounds), "aggregations": aggregated,
                  "check_s": time.perf_counter() - t_check,
                  "window_s": elapsed, "window_compiles": window_compiles,
                  **round_notes, **counters,
                  "route_gap": route_gap(prog_routes, want_steps["routes"], arch.first, arch.held),
                  "peer_deposits": list(peers.deposits), "reference_losses":
                  want_steps["losses"], "program_losses": prog_losses},
    }

