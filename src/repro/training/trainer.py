"""Local training loop with callback hooks (the Keras-fit analogue).

The federated layer (repro.core.FederatedCallback) plugs into
``on_epoch_end`` exactly as the paper plugs its FlwrFederatedCallback into
Keras. The loop itself is an ordinary jit'd JAX step; for distributed silos
the same Trainer accepts a Mesh + shardings (see repro.launch.train).
"""
from __future__ import annotations

import contextlib
import random as _random
import time
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import PyTree, tree_to_numpy
from repro.optim import Optimizer, apply_updates

LossFn = Callable[[PyTree, Any, jax.Array], tuple[jnp.ndarray, dict]]


@dataclass
class TrainState:
    params: PyTree
    opt_state: PyTree
    step: int = 0


def make_train_step(loss_fn: LossFn, optimizer: Optimizer):
    """(state, batch, rng) -> (state, metrics). Pure, jit-able."""

    def train_step(params, opt_state, batch, rng):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


class Trainer:
    def __init__(
        self,
        *,
        loss_fn: LossFn,
        optimizer: Optimizer,
        init_params: PyTree,
        eval_fn: Callable[[PyTree, Any], dict] | None = None,
        seed: int = 0,
        jit: bool = True,
        slowdown: float = 0.0,
        name: str = "trainer",
        telemetry=None,
    ):
        """``slowdown``: artificial seconds of sleep per step — used by the
        straggler experiments to make one node slower, as the paper does with
        heterogeneous hardware.

        ``telemetry``: an optional ``repro.core.telemetry.Telemetry`` — each
        ``run_epoch`` then records a ``train`` span. Usually the same instance
        the federated node carries."""
        self.optimizer = optimizer
        self.telemetry = telemetry
        self.eval_fn = eval_fn
        self.params = init_params
        self.opt_state = optimizer.init(init_params)
        self.step = 0
        self.name = name
        self.slowdown = slowdown
        self.rng = jax.random.PRNGKey(seed)
        self.rng_py = _random.Random(seed)
        self._train_step = make_train_step(loss_fn, optimizer)
        if jit:
            self._train_step = jax.jit(self._train_step)
        self.log: list[dict] = []
        self.crashed = False

    # -- params plumbing for federation --------------------------------------
    def host_params(self) -> PyTree:
        return tree_to_numpy(self.params)

    def set_params(self, params: PyTree) -> None:
        # Preserve leaf dtypes of the live params (store may hold f32 numpy).
        # Host-numpy leaves stay numpy: jnp.asarray would canonicalize
        # int64/float64 to 32-bit under the default jax config, silently
        # corrupting non-federated personal leaves that must round-trip
        # bit-exact (PartialFedAvg's exact-dtype passthrough).
        def _cast(old, new):
            if isinstance(old, (np.ndarray, np.generic)):
                return np.asarray(new, dtype=np.asarray(old).dtype)
            return jnp.asarray(new, dtype=old.dtype)

        self.params = jax.tree.map(_cast, self.params, params)

    # -- core loop ------------------------------------------------------------
    def run_epoch(self, batches: Iterable, steps: int | None = None) -> dict:
        # Metric values stay on device for the whole epoch: a per-step
        # float(v) would block on each step's result and serialize JAX's
        # async dispatch. One device_get at the end pays one sync, and the
        # ``train`` span ends with it.
        tel = self.telemetry
        step_metrics: list[dict] = []
        count = 0
        with tel.span("train") if tel is not None else contextlib.nullcontext():
            for i, batch in enumerate(batches):
                if steps is not None and i >= steps:
                    break
                self.rng, step_rng = jax.random.split(self.rng)
                self.params, self.opt_state, metrics = self._train_step(
                    self.params, self.opt_state, batch, step_rng
                )
                if self.slowdown:
                    time.sleep(self.slowdown)
                self.step += 1
                count += 1
                step_metrics.append(metrics)
            step_metrics = jax.device_get(step_metrics)
        metrics_acc: dict[str, float] = {}
        for metrics in step_metrics:
            for k, v in metrics.items():
                metrics_acc[k] = metrics_acc.get(k, 0.0) + float(v)
        return {k: v / max(1, count) for k, v in metrics_acc.items()}

    def fit(
        self,
        data_fn: Callable[[int], Iterable] | Iterable,
        *,
        epochs: int,
        steps_per_epoch: int | None = None,
        callbacks: Sequence = (),
        crash_at_epoch: int | None = None,
        verbose: bool = False,
    ) -> list[dict]:
        """Run local training with end-of-epoch callback hooks.

        ``data_fn`` is either a callable epoch→iterable (fresh shuffling per
        epoch) or a single reusable iterable. ``crash_at_epoch`` injects a
        failure for the robustness experiments.
        """
        for cb in callbacks:
            cb.on_train_begin(self)
        try:
            for epoch in range(epochs):
                if crash_at_epoch is not None and epoch >= crash_at_epoch:
                    self.crashed = True
                    raise RuntimeError(f"{self.name}: injected crash at epoch {epoch}")
                for cb in callbacks:
                    cb.on_epoch_begin(self, epoch)
                batches = data_fn(epoch) if callable(data_fn) else data_fn
                logs = self.run_epoch(batches, steps_per_epoch)
                if self.eval_fn is not None:
                    logs.update(self.eval_fn(self.params, None))
                logs["epoch"] = epoch
                self.log.append(logs)
                if verbose:
                    print(f"[{self.name}] epoch {epoch}: " + ", ".join(f"{k}={v:.4f}" for k, v in logs.items() if isinstance(v, float)))
                for cb in callbacks:
                    cb.on_epoch_end(self, epoch, logs)
        finally:
            # Teardown even on an injected crash: a FederatedCallback must
            # get the chance to stop its node's prefetcher thread.
            for cb in callbacks:
                cb.on_train_end(self)
        return self.log
