"""The public facade: one place where the store/transport/serving grammar
is documented, validated, and dispatched.

Everything below exists as lower-level constructors too (``WeightStore``,
``ShardedWeightStore``, ``make_folder``, ``ServingNode``) and those keep
working — but new code should come through here, because this is the one
spot where the three mini-languages meet:

**Folder-URI stages** (``connect(uri)``), outermost-first, ``+``-chained::

    [shard<G>[x<L>]+][retry+|cache+ ...]<base>

    ============  =====================================================
    stage         meaning
    ============  =====================================================
    shard<G>+     partition the fleet into G node-group folders with
                  ring gossip of group summaries (O(group) scans)
    shard<G>x<L>+ same, gossiping through an L-level summary tree
                  (planetary scale; must be the OUTERMOST stage)
    retry+        capped exponential-backoff retries on transient I/O
    cache+        read-through blob cache in front of the base folder
    <base>        ``memory://`` (anonymous, fresh per call) |
                  ``memory://<name>`` (process-global shared registry) |
                  ``s3://bucket/prefix`` | a local path
    ============  =====================================================

**Transport pipeline specs** (``connect(..., transport=...)``), innermost
policy stage plus optional envelope, ``|``-chained::

    "delta(chain=4)|zstd"      delta chains, zstd envelope
    "topk(adaptive)"           adaptive sparse top-k
    "family(adapters=topk)"    per-leaf-family sub-policies
    full | quantized | delta | delta_q | topk      (legacy names, mapped)

**Leaf-family selectors** (``connect(..., families=...)``): a registered
family name (``"adapters"``, ``"embeddings"``, ``"norms"``, or anything
``register_family``-ed), a sequence of names, or a ``{name: sub-policy}``
mapping — sugar for the ``family(...)`` transport stage above.

``connect`` returns the right store kind for the URI (sharded URIs →
``ShardedWeightStore``); ``serve`` turns any store + arch into a running
:class:`~repro.serving.ServingNode`.
"""
from __future__ import annotations

from typing import Any

from repro.core.gossip import ShardedFolders, ShardedWeightStore
from repro.core.store import WeightStore, make_folder
from repro.core.telemetry import Telemetry
from repro.core.transport import (
    family_transport_spec,
    normalize_transport,
    parse_folder_uri,
)

__all__ = ["connect", "serve"]


def connect(
    uri: str,
    *,
    transport: str | None = None,
    families: Any = None,
    prefetch: "bool | float | tuple[float, str] | None" = None,
    telemetry: "Telemetry | bool | None" = None,
    quantized: bool = False,
    keep_history: bool = False,
    compress: str = "none",
    **store_kwargs: Any,
):
    """Open a weight store behind any folder URI the grammar accepts.

    Parameters
    ----------
    uri:
        Folder URI — see the stage table in the module docstring. The full
        grammar is validated here; a malformed URI raises ``ValueError``
        before any folder is created.
    transport:
        Pipeline spec string or legacy name (``full``/``quantized``/
        ``delta``/``delta_q``/``topk``). Normalized to the canonical spec,
        so legacy names and their spec spellings are interchangeable.
    families:
        Leaf-family selector — sugar for ``transport="family(...)"``.
        Mutually exclusive with ``transport``.
    prefetch:
        Background cache warming: ``True`` (default interval), a float
        interval in seconds, or ``(interval, node_id)`` — the tuple form is
        required for sharded stores, whose prefetch is scoped to one node's
        home group.
    telemetry:
        A :class:`Telemetry` to attach, or ``True`` to create and attach one
        (reachable afterwards as ``store.telemetry``).
    quantized, keep_history, compress, **store_kwargs:
        Forwarded to the store constructor (``rebase_every``,
        ``topk_fraction``, ``decode_cache_entries``, ...).

    Returns the store: ``ShardedWeightStore`` for ``shard...+`` URIs,
    ``WeightStore`` otherwise.
    """
    parse_folder_uri(uri)  # validate the whole URI up front (clear errors)
    if families is not None:
        if transport is not None:
            raise ValueError("pass either transport= or families=, not both")
        transport = family_transport_spec(families)
    elif transport is not None:
        # normalize eagerly so a bad spec fails here, not at first push;
        # legacy names (full/quantized/...) map to their canonical specs
        transport = normalize_transport(transport)
    elif quantized:
        # legacy quantized=True flag → canonical spec, so it works uniformly
        # for sharded stores too (whose ctor has no quantized kwarg)
        transport = normalize_transport(None, quantized=True)

    folder = make_folder(uri)
    if isinstance(folder, ShardedFolders):
        store = ShardedWeightStore(
            folder,
            transport=transport,
            keep_history=keep_history,
            compress=compress,
            **store_kwargs,
        )
    else:
        store = WeightStore(
            folder,
            transport=transport,
            keep_history=keep_history,
            compress=compress,
            **store_kwargs,
        )

    if telemetry:
        tel = telemetry if isinstance(telemetry, Telemetry) else Telemetry(enabled=True)
        store.attach_telemetry(tel)

    if prefetch:
        if isinstance(prefetch, tuple):
            interval, node_id = prefetch
            store.start_prefetch(float(interval), exclude=node_id)
        elif isinstance(folder, ShardedFolders):
            raise ValueError(
                "sharded stores scope prefetch to one node's home group: "
                "pass prefetch=(interval, node_id)"
            )
        else:
            interval = 0.1 if prefetch is True else float(prefetch)
            store.start_prefetch(interval)
    return store


def serve(
    store,
    arch,
    *,
    node_id: str | None = None,
    reduced: bool = False,
    poll_interval: float = 0.25,
    telemetry: "Telemetry | bool | None" = None,
    mesh=None,
    start: bool = True,
    wait: float | None = None,
    **node_kwargs: Any,
):
    """Join a store read-only as a serving node.

    ``store`` is a store instance or a ``connect()``-able URI; ``arch`` an
    arch name from ``repro.configs`` or a full ``ModelConfig``. With
    ``start=True`` (default) the watcher thread is already running on
    return; ``wait`` additionally blocks up to that many seconds for the
    first weight set to go live. Returns the :class:`ServingNode`.
    """
    from repro.serving import ServingNode

    if isinstance(store, str):
        store = connect(store)
    node = ServingNode(
        store,
        arch,
        node_id=node_id,
        reduced=reduced,
        poll_interval=poll_interval,
        telemetry=telemetry,
        mesh=mesh,
        **node_kwargs,
    )
    if start:
        node.start()
        if wait is not None:
            node.wait_until_deployed(wait)
    return node
