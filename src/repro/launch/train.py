"""Federated training launcher.

Each federated *silo* is one process owning a device mesh; silos share only a
weight-store folder (DiskFolder on a shared mount in production, InMemoryFolder
under --simulate threading). This is the paper's serverless workflow scaled to
pjit-distributed nodes:

    # two real silos on two machines, shared NFS/gcsfuse mount:
    python -m repro.launch.train --arch pythia-14m --node-id silo0 --num-nodes 2 \
        --store /mnt/shared/exp1 --mode async --strategy fedavg
    python -m repro.launch.train ... --node-id silo1 ...

    # single-process simulation of N silos (paper's setup):
    python -m repro.launch.train --arch pythia-14m --simulate 3 --mode async
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.core import (
    AsyncFederatedNode,
    FederatedCallback,
    InMemoryFolder,
    SyncFederatedNode,
    get_strategy,
    make_folder,
    run_threaded,
)
from repro.core.partition import partition_sequence_dataset
from repro.data import lm_batch_iterator, make_synthetic_wikitext
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import adamw, with_accumulation
from repro.training import Trainer
from repro.configs import get_config


def make_lm_trainer(cfg, tokens, *, seq_len, batch_size, seed, lr, accum=1, slowdown=0.0, name="node"):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt = with_accumulation(adamw(lr), accum)

    def loss_fn(p, batch, rng):
        return model.loss(p, batch)

    trainer = Trainer(loss_fn=loss_fn, optimizer=opt, init_params=params, seed=seed,
                      slowdown=slowdown, name=name)

    def data_fn(epoch):
        return lm_batch_iterator(tokens, batch_size=batch_size, seq_len=seq_len,
                                 seed=seed, epoch=epoch)

    return trainer, data_fn


def evaluate_lm(cfg, params, tokens, *, seq_len, batch_size=8, max_batches=8):
    model = build_model(cfg)
    accs, losses = [], []
    for i, batch in enumerate(
        lm_batch_iterator(tokens, batch_size=batch_size, seq_len=seq_len, seed=999)
    ):
        if i >= max_batches:
            break
        loss, metrics = model.loss(params, batch)
        losses.append(float(loss))
        accs.append(float(metrics["accuracy"]))
    return {"eval_loss": float(np.mean(losses)), "eval_accuracy": float(np.mean(accs))}


def run_client(cfg, node_id, index, folder, args, tokens_shard, eval_tokens):
    """Train silo ``node_id``; ``index`` (its place among the silos) seeds it."""
    strategy = get_strategy(args.strategy)
    if args.mode == "sync":
        node = SyncFederatedNode(strategy=strategy, shared_folder=folder, node_id=node_id,
                                 num_nodes=args.num_nodes, timeout=args.timeout)
    else:
        node = AsyncFederatedNode(strategy=strategy, shared_folder=folder, node_id=node_id)
    trainer, data_fn = make_lm_trainer(
        cfg, tokens_shard, seq_len=args.seq_len, batch_size=args.batch_size,
        seed=args.seed + index, lr=args.lr, accum=args.accum,
        name=node_id,
    )
    steps = args.steps_per_epoch
    num_examples = steps * args.batch_size
    cb = FederatedCallback(node, num_examples_per_epoch=num_examples)
    trainer.fit(data_fn, epochs=args.epochs, steps_per_epoch=steps, callbacks=[cb],
                verbose=args.verbose)
    metrics = evaluate_lm(cfg, trainer.params, eval_tokens, seq_len=args.seq_len)
    return {"node": node_id, "pushes": node.num_pushes, "aggregations": node.num_aggregations,
            **metrics}


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="pythia-14m")
    ap.add_argument("--mode", default="async", choices=["async", "sync"])
    ap.add_argument("--strategy", default="fedavg")
    ap.add_argument("--store", default="memory://")
    ap.add_argument("--node-id", default=None, help="run as ONE real silo (production)")
    ap.add_argument("--num-nodes", type=int, default=2)
    ap.add_argument("--simulate", type=int, default=0, help="simulate N silos via threads")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--steps-per-epoch", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=None,
                    help="cut the vocab to this size (default: the config's own)")
    ap.add_argument("--lr", type=float, default=2e-5)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true", help="use the reduced config")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.vocab is not None:
        cfg = cfg.replace(vocab_size=min(cfg.vocab_size, args.vocab))

    data = make_synthetic_wikitext(vocab_size=cfg.vocab_size, seed=args.seed)
    num_nodes = args.simulate or args.num_nodes
    shards = partition_sequence_dataset(data.train_tokens, num_nodes)

    if args.simulate:
        folder = InMemoryFolder() if args.store == "memory://" else make_folder(args.store)
        args.num_nodes = num_nodes
        fns = [
            (lambda i=i: run_client(cfg, f"node{i}", i, folder, args, shards[i], data.test_tokens))
            for i in range(num_nodes)
        ]
        results = run_threaded(fns, names=[f"node{i}" for i in range(num_nodes)])
        for r in results:
            if r.error:
                print(f"[{r.node_id}] FAILED: {r.error}")
            else:
                print(json.dumps(r.result))
        return 1 if any(r.error for r in results) else 0

    if args.node_id is None:
        ap.error("need --node-id (production) or --simulate N")
    idx = int(args.node_id[-1]) if args.node_id[-1].isdigit() else 0
    folder = make_folder(args.store)
    result = run_client(cfg, args.node_id, idx, folder, args, shards[idx % num_nodes],
                        data.test_tokens)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
