"""Federated aggregation strategies, executed CLIENT-SIDE (serverless).

The paper's design makes the strategy a per-node object: every client owns a
strategy instance and aggregates whatever it pulls from the weight store with
its own weights inserted (Algorithm 1, ``WeightUpdate``). There is no server
state — "server-side" optimizers (FedAvgM / FedAdam / FedYogi / FedAdagrad,
Reddi et al. 2021) therefore keep their momentum/moment buffers *on the
client*, which is exactly the paper's "each client may implement its own
aggregation strategy" property.

Execution model: the store pulls ``FlatUpdate``s — contiguous f32 vectors
sharing one interned ``LeafSpec`` per model structure — and the node's own
update is a tree of host leaves in the model's dtypes. Every combine
``Σ_k c_k·x_k`` is one pass (``_combine``): each update is read where it lies
(a peer's flat in place, own's leaves in their own dtype), each element is
accumulated in f32, and the result is stored once, straight into leaves of the
spec's dtypes or, for the strategies that keep an f32 average as state
(FedAvgM and the FedOpt family), into their f32 vector. Big leaves are cut
into fixed chunks that run on the codec pool; a model whose chunks are all
small runs on the caller's thread. ``use_kernel=True`` (plumbed through the
``Strategy`` base so *every* strategy honors it) instead stacks the updates
into a (K, N) array for one Pallas ``fed_agg`` launch, or the fused
pseudo-gradient+moment kernel (``fed_opt``). Adaptive strategies keep their
momentum/moment buffers as flat vectors. The per-leaf reference
implementations live in ``strategies_ref.py`` (property-tested to match
within 1e-6).

Aggregation arithmetic is float32 — the same contract as the Pallas kernels
and the wire transports (quantized/delta values are f32-centric). Models with
leaves that don't embed exactly in f32 (int, f64) still aggregate (cast in,
cast back out), matching the PR-2 ``use_kernel`` behavior; PartialFedAvg
additionally passes such *personal* leaves through untouched.

Beyond-paper extensions (paper §5 limitations #2, and future work):
  * ``FedAsync``   — staleness-discounted mixing (Xie et al. 2019), executed
    as a single linear combination (the per-peer lerp chain factorizes into
    per-client coefficients — one fused pass instead of K).
  * ``FedBuff``    — buffered aggregation (Nguyen et al. 2022).
  * ``PartialFedAvg`` — partial model updates (Pillutla et al. 2022): only a
    filtered subset of leaves federates; the rest stay personal (a cached
    boolean mask over the flat index space).
"""
from __future__ import annotations

import re
from abc import ABC, abstractmethod
from typing import Sequence

import jax
import numpy as np

from .serialize import _INLINE_BYTES, NodeUpdate, _run_tasks
from .tree import LeafSpec, PyTree


# Elements of one combine task at most. A task whose f32 accumulator holds
# ``_INLINE_BYTES`` or more runs on the codec pool, a smaller one (a small
# leaf, a big leaf's tail) on the caller's thread: ``_run_tasks``'s rule.
_CHUNK = 1 << 20


def _leaf_sources(spec: LeafSpec, u: NodeUpdate) -> list[np.ndarray]:
    """Each leaf of ``u`` as a 1-D array where it lies: slices of a flat
    that ``spec`` describes, else the tree's own leaves in their own dtype."""
    flat = getattr(u, "flat", None)
    if flat is not None and spec.compatible(u.spec):
        return [flat[o:o + n] for o, n in zip(spec.offsets.tolist(), spec.sizes)]
    leaves = jax.tree.leaves(u.params)
    if len(leaves) != len(spec.sizes):
        raise ValueError(f"{len(leaves)} leaves vs spec's {len(spec.sizes)}")
    out = []
    for n, leaf in zip(spec.sizes, leaves):
        arr = np.asarray(leaf).reshape(-1)
        if arr.size != n:
            raise ValueError(f"leaf size {arr.size} vs spec's {n}")
        out.append(arr)
    return out


def _axpy(dst: np.ndarray, srcs: list, coeffs: list) -> None:
    """dst = Σ_k coeffs[k]·srcs[k], each source widened to f32 and the sum
    accumulated in f32 in the sources' order, then cast to ``dst``'s dtype."""
    acc = dst if dst.dtype == np.float32 else np.empty(dst.size, np.float32)
    np.multiply(srcs[0], coeffs[0], out=acc, dtype=np.float32)
    if len(srcs) > 1:
        tmp = np.empty(dst.size, np.float32)
        for src, c in zip(srcs[1:], coeffs[1:]):
            np.multiply(src, c, out=tmp, dtype=np.float32)
            acc += tmp
    if acc is not dst:
        np.copyto(dst, acc, casting="unsafe")


def _combine(spec: LeafSpec, updates: Sequence[NodeUpdate], coeffs: np.ndarray,
             dst: list, leaves: Sequence[int]) -> int:
    """Σ_k coeffs[k]·updates[k] — THE aggregation primitive — into ``dst[i]``
    (1-D, any dtype) for each leaf index ``i`` in ``leaves``. One pass: each
    update is read where it lies (``_leaf_sources``), each element
    accumulated in f32 and stored once. Leaves are cut into chunks of
    ``_CHUNK`` elements that run on the codec pool, the rest on the caller's
    thread; the result does not depend on the cut. The coefficients need not
    be normalized, which is what lets FedAsync's lerp chain and weighted
    means share this code. Returns the number of chunks sent to the pool."""
    sources = [_leaf_sources(spec, u) for u in updates]
    cs = [np.float32(c) for c in coeffs]
    tasks = []
    for i in leaves:
        out, srcs = dst[i], [s[i] for s in sources]
        for a in range(0, out.size, _CHUNK):
            b = a + _CHUNK
            tasks.append((4 * (min(b, out.size) - a), _axpy,
                          (out[a:b], [s[a:b] for s in srcs], cs)))
    _run_tasks(tasks)
    return sum(t[0] >= _INLINE_BYTES for t in tasks)


def _stack(spec: LeafSpec, updates: Sequence[NodeUpdate]) -> np.ndarray:
    """The (K, N) f32 rows the ``fed_agg`` kernels take."""
    stacked = np.empty((len(updates), spec.num_params), np.float32)
    for row, u in zip(stacked, updates):
        flat = getattr(u, "flat", None)
        if flat is not None and spec.compatible(u.spec):
            row[:] = flat
        else:
            spec.flatten_into(u.params, row)
    return stacked


class Strategy(ABC):
    """Client-side aggregation strategy (flat-vector execution).

    ``use_kernel`` lives on the base class so every subclass — not just
    FedAvg — routes its linear combinations through the Pallas ``fed_agg`` /
    ``fed_opt`` kernels when asked.
    """

    name: str = "strategy"
    # chunks the combine has sent to the codec pool over this strategy's life
    pool_chunks: int = 0

    def __init__(self, *, use_kernel: bool = False, reuse_output: bool = False):
        self.use_kernel = use_kernel
        # reuse_output=True returns trees whose leaves are strategy-owned,
        # valid only until the next aggregate() call — the steady-state fast
        # path for trainers that consume the aggregate immediately (e.g. copy
        # it to device). Default False: every aggregate returns fresh storage.
        self.reuse_output = reuse_output
        self._spec: LeafSpec | None = None
        self._bufs: dict[str, np.ndarray] = {}
        self._reused: tuple[str, list] | None = None  # (spec key, leaves)

    # -- flat plumbing -------------------------------------------------------
    def _resolve_spec(self, own: NodeUpdate) -> LeafSpec:
        """The layout everything is aggregated in: own's spec when the store
        handed us a FlatUpdate, else a spec built once and reused while own's
        structure is stable."""
        spec = getattr(own, "spec", None)
        if spec is not None:
            self._spec = spec
            return spec
        spec = self._spec
        if spec is not None and spec.describes(own.params):
            return spec
        spec = LeafSpec.of(own.params)
        self._spec = spec
        return spec

    def _flat_of(self, u: NodeUpdate, spec: LeafSpec) -> np.ndarray:
        flat = getattr(u, "flat", None)
        if flat is not None and spec.compatible(u.spec):
            return flat
        return spec.flatten(u.params)

    def _buffer(self, name: str, spec: LeafSpec) -> np.ndarray:
        """Named warm scratch vector (internal use — never escapes unless
        ``reuse_output`` opted in)."""
        buf = self._bufs.get(name)
        if buf is None or buf.size != spec.num_params:
            buf = np.empty(spec.num_params, np.float32)
            self._bufs[name] = buf
        return buf

    def _out_leaves(self, spec: LeafSpec) -> list[np.ndarray]:
        """Leaves of the spec's shapes and dtypes to write an aggregate into:
        fresh, or under ``reuse_output`` the strategy's own."""
        if self._reused is not None and self._reused[0] == spec.key:
            return self._reused[1]
        leaves = [np.empty(s, d) for s, d in zip(spec.shapes, spec.dtypes)]
        if self.reuse_output:
            self._reused = (spec.key, leaves)
        return leaves

    def _emit(self, spec: LeafSpec, state: np.ndarray) -> np.ndarray:
        """Detach internal state for the caller: a fresh copy by default, the
        reusable out buffer under ``reuse_output``."""
        if self.reuse_output:
            out = self._buffer("out", spec)
            np.copyto(out, state)
            return out
        return state.copy()

    def _mean_coeffs(self, updates: Sequence[NodeUpdate]) -> np.ndarray:
        weights = np.asarray([max(1, u.num_examples) for u in updates], np.float32)
        return weights / weights.sum()

    def _kernel_combine(self, spec: LeafSpec, updates: Sequence[NodeUpdate],
                        coeffs: np.ndarray) -> np.ndarray | None:
        """Σ_k coeffs[k]·updates[k] by one ``fed_agg`` launch over the
        stacked updates, when ``use_kernel`` asks for it; else None."""
        if not self.use_kernel or len(updates) < 2:
            return None
        from repro.kernels.fed_agg import ops as fed_agg_ops

        return np.asarray(fed_agg_ops.aggregate_flat(_stack(spec, updates), coeffs))

    def _combine_into(self, spec: LeafSpec, updates: Sequence[NodeUpdate],
                      coeffs: np.ndarray, out: np.ndarray) -> np.ndarray:
        """Σ_k coeffs[k]·updates[k] as an f32 vector in the spec's layout:
        ``out``, or the kernel's own."""
        vec = self._kernel_combine(spec, updates, coeffs)
        if vec is not None:
            return vec
        offsets = spec.offsets.tolist()
        self.pool_chunks += _combine(
            spec, updates, coeffs,
            [out[o:o + n] for o, n in zip(offsets, spec.sizes)], range(len(offsets)))
        return out

    def _combine_leaves(self, spec: LeafSpec, updates: Sequence[NodeUpdate],
                        coeffs: np.ndarray, leaves: Sequence[int] | None = None,
                        ) -> list[np.ndarray]:
        """Σ_k coeffs[k]·updates[k] in leaves of the spec's shapes and dtypes
        (``_out_leaves``); only the leaf indices in ``leaves`` (all by
        default) are written."""
        out = self._out_leaves(spec)
        if leaves is None:
            leaves = range(len(out))
        vec = self._kernel_combine(spec, updates, coeffs)
        if vec is None:
            self.pool_chunks += _combine(spec, updates, coeffs,
                                         [a.reshape(-1) for a in out], leaves)
            return out
        for i in leaves:
            o, n = int(spec.offsets[i]), spec.sizes[i]
            np.copyto(out[i].reshape(-1), vec[o:o + n], casting="unsafe")
        return out

    def _mean_tree(self, spec: LeafSpec, updates: Sequence[NodeUpdate]) -> PyTree:
        """Example-count weighted mean (FedAvg, eq. 1) as the model's tree."""
        return jax.tree.unflatten(
            spec.treedef, self._combine_leaves(spec, updates, self._mean_coeffs(updates)))

    @abstractmethod
    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        """Combine own latest params with peer updates → new local params."""

    # -- recoverable optimizer state ------------------------------------------
    def state_dict(self) -> dict[str, np.ndarray] | None:
        """Named flat vectors a restarted node needs to resume this
        strategy's server-optimizer trajectory (momentum/moment buffers).
        ``None`` when stateless (nothing worth persisting); the node ships
        the dict as a ``state/<node>`` recovery blob through the transport
        pipeline. Stateful subclasses override both hooks."""
        return None

    def load_state_dict(self, state: dict) -> None:
        """Restore ``state_dict`` output (a best-effort no-op on mismatch:
        a recovered blob from an older structure must never crash a fresh
        node — it just starts cold)."""

    @staticmethod
    def _flat_state(state: dict, *names: str) -> "list[np.ndarray] | None":
        """Validate + normalize recovery arrays: all present, equal sizes."""
        try:
            vecs = [np.asarray(state[n], np.float32).reshape(-1).copy()
                    for n in names]
        except (KeyError, TypeError, ValueError):
            return None
        if len({v.size for v in vecs}) != 1:
            return None
        return vecs

    def reset(self) -> None:  # stateful subclasses extend
        self._spec = None
        self._bufs.clear()
        self._reused = None


class FedAvg(Strategy):
    """Example-count weighted average (McMahan et al. 2016, eq. 1)."""

    name = "fedavg"

    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        return self._mean_tree(self._resolve_spec(own), [own, *peers])


class _FedOpt(Strategy):
    """Adaptive federated optimization base (Reddi et al. 2021).

    Maintains a client-local estimate x of the global model. Each aggregation
    computes the pseudo-gradient Δ = x − avg(updates) and applies a server
    optimizer step to x. ``x``/``m``/``v`` are flat f32 vectors, lazily
    initialized from the first own update; with ``use_kernel`` the whole
    avg→Δ→moments→step chain runs as the fused ``fed_opt`` Pallas kernel
    (one pass over the stacked updates).
    """

    variant: str = "adam"

    def __init__(self, server_lr: float = 1.0, beta1: float = 0.9,
                 beta2: float = 0.99, tau: float = 1e-3, *, use_kernel: bool = False):
        super().__init__(use_kernel=use_kernel)
        self.server_lr = server_lr
        self.beta1 = beta1
        self.beta2 = beta2
        self.tau = tau
        self.x: np.ndarray | None = None
        self.m: np.ndarray | None = None
        self.v: np.ndarray | None = None

    def reset(self) -> None:
        super().reset()
        self.x = self.m = self.v = None

    def state_dict(self) -> dict[str, np.ndarray] | None:
        if self.x is None:
            return None
        return {"x": self.x, "m": self.m, "v": self.v}

    def load_state_dict(self, state: dict) -> None:
        vecs = self._flat_state(state, "x", "m", "v")
        if vecs is not None:
            self.x, self.m, self.v = vecs

    def _update_v(self, v: np.ndarray, d2: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        spec = self._resolve_spec(own)
        if self.x is not None and self.x.size != spec.num_params:
            self.x = self.m = self.v = None  # structure changed → reinit
        updates = [own, *peers]
        coeffs = self._mean_coeffs(updates)
        if self.x is None:
            self.x = np.array(self._flat_of(own, spec), np.float32, copy=True)
            self.m = np.zeros_like(self.x)
            self.v = np.zeros_like(self.x)
        if self.use_kernel:
            from repro.kernels.fed_agg import ops as fed_agg_ops

            self.x, self.m, self.v = fed_agg_ops.fed_opt_flat(
                _stack(spec, updates), coeffs, self.x, self.m, self.v,
                variant=self.variant, server_lr=self.server_lr,
                beta1=self.beta1, beta2=self.beta2, tau=self.tau,
            )
            # fed_opt_flat returned freshly allocated state nothing aliases,
            # and the kernel path replaces (never mutates) it next round — no
            # detach copy needed
            return spec.unflatten(self.x)
        else:
            avg = self._combine_into(spec, updates, coeffs, self._buffer("avg", spec))
            d = self.x - avg  # pseudo-gradient
            self.m *= self.beta1
            self.m += (1.0 - self.beta1) * d
            self.v = self._update_v(self.v, d * d)
            self.x -= self.server_lr * self.m / (np.sqrt(self.v) + self.tau)
        return spec.unflatten(self._emit(spec, self.x))  # in-place state: detach


class FedAvgM(Strategy):
    """FedAvg with server momentum (Hsu et al. 2019)."""

    name = "fedavgm"

    def __init__(self, server_lr: float = 1.0, momentum: float = 0.9, *,
                 use_kernel: bool = False):
        super().__init__(use_kernel=use_kernel)
        self.server_lr = server_lr
        self.momentum = momentum
        self.x: np.ndarray | None = None
        self.buf: np.ndarray | None = None

    def reset(self) -> None:
        super().reset()
        self.x = self.buf = None

    def state_dict(self) -> dict[str, np.ndarray] | None:
        if self.x is None:
            return None
        return {"x": self.x, "buf": self.buf}

    def load_state_dict(self, state: dict) -> None:
        vecs = self._flat_state(state, "x", "buf")
        if vecs is not None:
            self.x, self.buf = vecs

    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        spec = self._resolve_spec(own)
        if self.x is not None and self.x.size != spec.num_params:
            self.x = self.buf = None
        updates = [own, *peers]
        avg = self._combine_into(spec, updates, self._mean_coeffs(updates),
                                 self._buffer("avg", spec))
        if self.x is None:
            self.x = np.array(self._flat_of(own, spec), np.float32, copy=True)
            self.buf = np.zeros_like(self.x)
        # buf = momentum·buf + (x − avg);  x -= lr·buf   (all in place)
        self.buf *= self.momentum
        self.buf += self.x
        self.buf -= avg
        self.x -= self.server_lr * self.buf
        return spec.unflatten(self._emit(spec, self.x))


class FedAdam(_FedOpt):
    name = "fedadam"
    variant = "adam"

    def _update_v(self, v, d2):
        return self.beta2 * v + (1 - self.beta2) * d2


class FedYogi(_FedOpt):
    name = "fedyogi"
    variant = "yogi"

    def _update_v(self, v, d2):
        return v - (1 - self.beta2) * d2 * np.sign(v - d2)


class FedAdagrad(_FedOpt):
    name = "fedadagrad"
    variant = "adagrad"

    def _update_v(self, v, d2):
        return v + d2


class FedAsync(Strategy):
    """Staleness-aware asynchronous mixing (Xie et al. 2019, FedAsync).

    new = (1 − α_k)·current + α_k·peer_k, applied per peer in arrival order,
    with α_k = alpha·s(staleness) and s a polynomial/hinge discount.
    Staleness is measured in counter lag (peer.counter vs own.counter).

    The sequential lerp chain factorizes exactly into one linear combination:
    c_own = Π_j (1 − α_j) and c_k = α_k·Π_{j>k} (1 − α_j), so the whole chain
    is a single combine (per-*client* work stays a trivial K-length Python
    loop computing coefficients).

    Elastic-fleet churn adds a second discount axis: a peer whose
    ``lease_epoch`` is *ahead* of ours was adopted by a surviving worker and
    resumed from its stranded ``latest/`` blob — its params may encode a
    trajectory frozen long before its counter suggests. Each adoption hop
    multiplies that peer's mixing weight by ``(1 + epoch_gap)^(-epoch_a)``
    (one-sided: only peers *ahead* in epochs are damped, so the resurrected
    node itself still absorbs the live consensus at full strength instead of
    yanking it backwards). ``epoch_a = 0`` disables the term; updates without
    lease metadata (gap 0) aggregate bit-identically to before.
    """

    name = "fedasync"

    def __init__(self, alpha: float = 0.6, staleness_fn: str = "poly",
                 a: float = 0.5, b: int = 4, *, epoch_a: float = 1.0,
                 use_kernel: bool = False):
        super().__init__(use_kernel=use_kernel)
        self.alpha = alpha
        self.staleness_fn = staleness_fn
        self.a = a
        self.b = b
        self.epoch_a = epoch_a

    def _discount(self, staleness: float) -> float:
        s = max(0.0, staleness)
        if self.staleness_fn == "poly":
            return (1.0 + s) ** (-self.a)
        if self.staleness_fn == "hinge":
            return 1.0 if s <= self.b else 1.0 / (self.a * (s - self.b) + 1.0)
        if self.staleness_fn == "const":
            return 1.0
        raise ValueError(f"unknown staleness_fn {self.staleness_fn}")

    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        if not peers:
            return own.params
        spec = self._resolve_spec(own)
        own_epoch = int(getattr(own, "lease_epoch", 0))
        alphas = []
        for peer in peers:
            a_eff = self.alpha * self._discount(float(own.counter - peer.counter))
            gap = int(getattr(peer, "lease_epoch", 0)) - own_epoch
            if gap > 0 and self.epoch_a:
                a_eff *= (1.0 + gap) ** (-self.epoch_a)
            alphas.append(min(max(a_eff, 0.0), 1.0))
        coeffs = np.empty(len(peers) + 1, np.float32)
        suffix = 1.0  # Π_{j>k} (1 − α_j), built back to front
        for k in range(len(peers) - 1, -1, -1):
            coeffs[k + 1] = alphas[k] * suffix
            suffix *= 1.0 - alphas[k]
        coeffs[0] = suffix
        return jax.tree.unflatten(
            spec.treedef, self._combine_leaves(spec, [own, *peers], coeffs))


class FedBuff(Strategy):
    """Buffered asynchronous aggregation (Nguyen et al. 2022).

    Accumulates peer updates into a buffer; only aggregates once ≥ K distinct
    updates (incl. own) have been buffered, otherwise returns own params
    unchanged (client keeps training).
    """

    name = "fedbuff"

    def __init__(self, buffer_size: int = 3, *, use_kernel: bool = False):
        super().__init__(use_kernel=use_kernel)
        self.buffer_size = buffer_size
        self._pending: dict[str, NodeUpdate] = {}
        self._seen_counters: dict[str, int] = {}

    def reset(self) -> None:
        super().reset()
        self._pending.clear()
        self._seen_counters.clear()

    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        for peer in peers:
            if self._seen_counters.get(peer.node_id, -1) < peer.counter:
                self._pending[peer.node_id] = peer
                self._seen_counters[peer.node_id] = peer.counter
        self._pending[own.node_id] = own
        if len(self._pending) < self.buffer_size:
            return own.params
        updates = list(self._pending.values())
        self._pending.clear()
        return self._mean_tree(self._resolve_spec(own), updates)


class PartialFedAvg(Strategy):
    """Partial model personalization (Pillutla et al. 2022): only leaves whose
    path matches ``shared_pattern`` federate; everything else stays personal.

    ``families=`` selects shared leaves by *named leaf family* instead (a
    family name, a sequence of names, or a ``{name: path-regex}`` mapping —
    see ``tree.FAMILY_PATTERNS``), resolved through ``LeafSpec.family_view``:
    the exact subset the ``family(...)`` transport ships, so the aggregation
    mask and the wire selector can never diverge. It overrides
    ``shared_pattern`` when given.

    The leaf filter runs once per spec; each aggregate then combines the
    shared leaves and copies own's personal leaves as they are, in their own
    dtype, never rounded through f32.
    """

    name = "partial_fedavg"

    def __init__(self, shared_pattern: str = ".*", *, families=None,
                 use_kernel: bool = False, reuse_output: bool = False):
        super().__init__(use_kernel=use_kernel, reuse_output=reuse_output)
        self.families = families
        self.pattern = re.compile(shared_pattern)
        self._split: tuple[str, list[int], list[int]] | None = None

    def _split_for(self, spec: LeafSpec) -> tuple[list[int], list[int]]:
        """(shared, personal) leaf indices of ``spec``."""
        if self._split is None or self._split[0] != spec.key:
            if self.families is not None:
                mask = spec.family_view(self.families).leaf_mask
            else:
                mask = [bool(self.pattern.search(p)) for p in spec.paths]
            self._split = (spec.key, [i for i, m in enumerate(mask) if m],
                           [i for i, m in enumerate(mask) if not m])
        return self._split[1], self._split[2]

    def aggregate(self, own: NodeUpdate, peers: Sequence[NodeUpdate]) -> PyTree:
        spec = self._resolve_spec(own)
        updates = [own, *peers]
        shared, personal = self._split_for(spec)
        out = self._combine_leaves(spec, updates, self._mean_coeffs(updates), shared)
        own_leaves = _leaf_sources(spec, own)
        for i in personal:
            np.copyto(out[i].reshape(-1), own_leaves[i], casting="unsafe")
        return jax.tree.unflatten(spec.treedef, out)


STRATEGIES = {
    cls.name: cls
    for cls in [FedAvg, FedAvgM, FedAdam, FedYogi, FedAdagrad, FedAsync, FedBuff, PartialFedAvg]
}


def get_strategy(name: str, **kwargs) -> Strategy:
    if name not in STRATEGIES:
        raise KeyError(f"unknown strategy {name!r}; options: {sorted(STRATEGIES)}")
    return STRATEGIES[name](**kwargs)
