"""Wire format for the weight store.

A deposited update is a pytree of numpy arrays plus scalar metadata
(num_examples, local epoch counter, node id, wall time). We serialize to a
single npz blob: leaves stored under their key-path strings, metadata under a
reserved ``__meta__`` JSON entry. Key-path keyed storage (instead of pickling
a treedef) keeps the format language- and process-agnostic — the store really
could be an S3 bucket written by heterogeneous clients.

One writer and one reader handle every blob. The writer (``_write_stored``)
lays the envelope out member by member and fills it in one pass, each leaf
cast straight into its slot and CRC'd as it is written, or deflated whole on
the codec pool: the bytes ``np.savez`` or ``np.savez_compressed`` would give.
The reader (``NpzEnvelope``) views each member's array in the blob's own bytes
(a deflated member in its bytes inflated once), checks the member's CRC-32
against the zip directory as ``zipfile`` would, and copies it once into the
flat vector or an array of its own. 'zstd' wraps the whole envelope in one
frame, undone before either looks at it.
"""
from __future__ import annotations

import functools
import hashlib
import io
import json
import os
import struct
import threading
import zipfile
import zlib
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from typing import Any, NamedTuple

import jax
import numpy as np
import zstandard
from numpy.lib import format as npy_format

from .tree import LeafSpec, PyTree, path_str

_META_KEY = "__meta__"
_SEP = "|"  # npz keys cannot contain '/' reliably across tools; use '|'

# zstd frame magic — lets deserializers sniff a zstd-wrapped npz envelope
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"

COMPRESSIONS = ("none", "npz", "zstd")


def _zstd_compress(blob: bytes) -> bytes:
    # contexts are built per call: zstandard contexts are not thread-safe,
    # and stores are shared across threads
    return zstandard.ZstdCompressor().compress(blob)


def maybe_decompress(blob: bytes) -> bytes:
    """Undo the optional zstd wire wrapping; readers stay format-agnostic."""
    if blob[:4] != _ZSTD_MAGIC:
        return blob
    return zstandard.ZstdDecompressor().decompress(blob)


@dataclass
class NodeUpdate:
    """One client's deposit in the weight store."""

    params: PyTree
    num_examples: int
    node_id: str
    counter: int = 0  # client-local epoch counter (no global round exists)
    timestamp: float = 0.0  # virtual or wall time, for staleness strategies
    metrics: dict = field(default_factory=dict)
    # Fleet-lease epoch of the writer: 0 for a node on its original claim,
    # bumped each time the node's slot was adopted by a surviving worker.
    # Staleness-aware strategies (FedAsync) discount resurrected stragglers
    # by the epoch gap so an adopted node's resumed-from-old params cannot
    # yank the consensus backwards.
    lease_epoch: int = 0


class FlatUpdate(NodeUpdate):
    """A ``NodeUpdate`` whose params live as one contiguous f32 vector plus a
    shared ``LeafSpec``. ``params`` materializes the pytree lazily (and caches
    it), so every existing reader keeps working; flat-aware consumers (the
    vectorized strategies) grab ``flat``/``spec`` directly and never touch a
    nested dict. Treat both the flat vector and the materialized tree as
    read-only — they may be shared via the store's decode cache."""

    def __init__(self, flat: np.ndarray, spec: LeafSpec, *, num_examples: int,
                 node_id: str, counter: int = 0, timestamp: float = 0.0,
                 metrics: dict | None = None, lease_epoch: int = 0):
        self.flat = np.asarray(flat, np.float32).reshape(-1)
        self.spec = spec
        self._tree: PyTree | None = None
        NodeUpdate.__init__(
            self, params=None, num_examples=num_examples, node_id=node_id,
            counter=counter, timestamp=timestamp, metrics=metrics or {},
            lease_epoch=lease_epoch,
        )

    @property
    def params(self) -> PyTree:
        if self._tree is None:
            self._tree = self.spec.unflatten(self.flat)
        return self._tree

    @params.setter
    def params(self, value) -> None:  # dataclass __init__ assigns params=None
        self._tree = value

    def __repr__(self) -> str:  # avoid materializing the tree for debugging
        return (f"FlatUpdate(node_id={self.node_id!r}, counter={self.counter}, "
                f"num_examples={self.num_examples}, spec={self.spec!r})")


def _wire_dtype(dtype: np.dtype) -> tuple[np.dtype, str | None]:
    """The dtype a leaf of ``dtype`` ships as. numpy cannot round-trip
    ml_dtypes through npz, so those ship as f32 (aggregation is f32 anyway);
    returns (wire dtype, original dtype name to restore on load — None when
    unneeded)."""
    if dtype.kind == "V" or dtype.name in ("bfloat16", "float8_e4m3fn", "float8_e5m2"):
        return np.dtype(np.float32), dtype.name
    return dtype, None


def _wire_leaf(leaf) -> tuple[np.ndarray, str | None]:
    """A leaf as its on-wire array, and the dtype name to restore on load."""
    arr = np.asarray(leaf)
    wire, restored = _wire_dtype(arr.dtype)
    return (arr.astype(wire) if restored else arr), restored


def _rebuild_tree(order, dtypes, get_leaf) -> dict:
    """Rebuild the nested-dict pytree from 'a|b|c' key paths; restores the
    original dtype of leaves that shipped as f32."""
    tree: dict = {}
    for key in order:
        leaf = get_leaf(key)
        if key in dtypes:
            import ml_dtypes

            leaf = leaf.astype(np.dtype(getattr(ml_dtypes, dtypes[key])))
        parts = key.split(_SEP)
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def _pack_npz(arrays: dict[str, np.ndarray], order: list[str], dtypes: dict[str, str],
              meta: dict[str, Any] | None, *, compress: str = "none") -> bytes:
    """The one wire envelope: leaf arrays + __order__/__dtypes__ under a JSON
    __meta__ entry, zipped into an npz. Full and delta blobs both go through
    here so envelope changes cannot desynchronize the two formats. Arrays may
    come in their own dtype: each ships as ``_wire_dtype`` says.

    ``compress``: 'none' (stored npz), 'npz' (each member deflated, as
    ``np.savez_compressed`` writes it), or 'zstd' (whole-blob zstd frame,
    sniffed by ``maybe_decompress``)."""
    if compress not in COMPRESSIONS:
        raise ValueError(f"unknown compress {compress!r}; options: {COMPRESSIONS}")
    meta_blob = dict(meta or {})
    meta_blob["__order__"] = order
    meta_blob["__dtypes__"] = dtypes
    arrays[_META_KEY] = np.frombuffer(json.dumps(meta_blob).encode(), dtype=np.uint8)
    blob = _write_stored(arrays, deflate=compress == "npz")
    if compress == "zstd":
        blob = _zstd_compress(blob)
    return blob


def serialize_params(params: PyTree, meta: dict[str, Any] | None = None, *,
                     compress: str = "none") -> bytes:
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(params)[0]
    arrays: dict[str, np.ndarray] = {}
    order: list[str] = []
    dtypes: dict[str, str] = {}
    for path, leaf in leaves_with_paths:
        key = path_str(path).replace("/", _SEP)
        arr = np.asarray(leaf)  # widened as it is written, not here
        original_dtype = _wire_dtype(arr.dtype)[1]
        if original_dtype:
            dtypes[key] = original_dtype
        arrays[key] = arr
        order.append(key)
    return _pack_npz(arrays, order, dtypes, meta, compress=compress)


def deserialize_params(blob: bytes) -> tuple[PyTree, dict[str, Any]]:
    """Returns (nested-dict params, meta). Key paths 'a|b|c' rebuild nesting."""
    env = NpzEnvelope(blob)
    meta, order, dtypes = env.contents()
    return _rebuild_tree(order, dtypes, env.read(order).__getitem__), meta


# --- the reader: npz envelope → arrays, in place ------------------------------
#
# ``NpzEnvelope`` reads every blob the program reads. The zip directory is
# parsed once; a member's ``.npy`` header on first use. Its array is a view of
# the blob's own bytes for a stored member, or of the member inflated once for
# a deflated one, so the only pass over the data is one copy into the flat (or
# an array of the caller's) plus the CRC-32 check against the directory that
# ``zipfile`` makes — the same ``zipfile.BadZipFile`` on a mismatch. A deflated
# member is checked as it is inflated, a stored one alongside its copy. Big
# copies and checks run on a small thread pool (``zlib.crc32`` and numpy's copy
# release the GIL). A member the writer cannot have produced (encrypted, not
# ``.npy``, a header past format 1.0, a dtype outside ``biufcmMSU``) raises
# ``ValueError``, as bytes that are not a zip do.
#
# The flat decode (``decode_params_flat``) reads a blob straight into one
# preallocated f32 vector laid out by a LeafSpec derived from the blob's own
# __order__/__dtypes__ metadata. Specs are interned in a caller-owned table,
# so every update a store decodes for the same model shares one spec instance
# and aggregation can stack flats with an identity check instead of a
# structural comparison.


class FlatDecodeUnsupported(ValueError):
    """Blob holds leaves a flat f32 vector cannot represent losslessly
    (int/f64 wire arrays) — callers fall back to the per-leaf tree decode."""


# zip local file header: signature, 22 bytes of fields the central directory
# repeats, then the lengths of the name and of the extra field that precede
# the member's data
_LOCAL_HEADER = struct.Struct("<4s22xHH")
_NPY_PREAMBLE_MAX = 1 << 14  # bytes sliced to parse one .npy header
_INLINE_BYTES = 1 << 20      # smaller checks and copies run on the caller's thread
_PIECE_BYTES = 1 << 25       # big leaves are copied in pieces of this many bytes of the output
_DTYPE_KINDS = "biufcmMSU"   # what a member may hold: no objects, no records

_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def _codec_pool() -> ThreadPoolExecutor:
    """The process's codec threads, shared by the reader and the writer,
    built on first use: one per usable core, at most 8."""
    global _pool
    with _pool_lock:
        if _pool is None:
            cores = (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
                     else os.cpu_count() or 1)
            _pool = ThreadPoolExecutor(max_workers=min(8, cores),
                                       thread_name_prefix="npz-codec")
        return _pool


class _Member(NamedTuple):
    """Where one member lies in its blob."""

    start: int          # first byte of the member's data
    stop: int
    crc: int            # CRC-32 of its .npy file, from the zip directory
    size: int           # bytes of its .npy file (stop - start unless deflated)
    deflated: bool


class _Array(NamedTuple):
    """Where one member's array lies once its .npy header is read."""

    buf: bytes          # the blob, or the member inflated
    offset: int         # first byte of the array's payload in ``buf``
    dtype: np.dtype
    shape: tuple
    order: str          # "F" for a Fortran-ordered array, else "C"


def _zip_members(blob: bytes) -> dict[str, _Member]:
    """Member key → extent. Reads only the zip directory and the local
    headers; raises ``zipfile.BadZipFile`` on a zip that is not whole."""
    if blob[:4] != b"PK\x03\x04":
        raise ValueError("not an npz envelope")
    with zipfile.ZipFile(io.BytesIO(blob)) as zf:
        infos = zf.infolist()
    members = {}
    for zi in infos:
        if (zi.compress_type not in (zipfile.ZIP_STORED, zipfile.ZIP_DEFLATED)
                or zi.flag_bits & 0x1 or not zi.filename.endswith(".npy")):
            raise ValueError(f"member {zi.filename!r} is not a plain .npy file")
        if zi.header_offset + _LOCAL_HEADER.size > len(blob):
            raise zipfile.BadZipFile(f"member {zi.filename!r} lies past the end of the blob")
        magic, name_len, extra_len = _LOCAL_HEADER.unpack_from(blob, zi.header_offset)
        if magic != b"PK\x03\x04":
            raise zipfile.BadZipFile(f"bad local header for member {zi.filename!r}")
        start = zi.header_offset + _LOCAL_HEADER.size + name_len + extra_len
        stop = start + zi.compress_size
        if stop > len(blob):
            raise zipfile.BadZipFile(f"member {zi.filename!r} runs past the end of the blob")
        members[zi.filename[:-len(".npy")]] = _Member(
            start, stop, zi.CRC, zi.file_size, zi.compress_type == zipfile.ZIP_DEFLATED)
    return members


def _inflate(blob: bytes, m: _Member, key: str) -> bytes:
    """Member ``m``'s .npy file inflated, checked against its CRC-32."""
    try:
        data = zlib.decompress(memoryview(blob)[m.start:m.stop], -15, m.size)
    except zlib.error as e:
        raise zipfile.BadZipFile(f"bad deflate stream for file {key}.npy: {e}") from None
    if len(data) != m.size or zlib.crc32(data) != m.crc:
        raise zipfile.BadZipFile(f"Bad CRC-32 for file {key}.npy")
    return data


def _copy_leaf(dst: np.ndarray, src: np.ndarray, scale) -> None:
    if scale is None:
        dst[...] = src
    else:  # int8-quantized leaf
        np.multiply(src, scale, out=dst, dtype=np.float32, casting="unsafe")


class NpzEnvelope:
    """One parse of a serialized blob's envelope: the zstd frame undone, the
    zip directory and the ``__meta__`` entry read. ``meta`` is shared: copy
    before mutating."""

    def __init__(self, blob: bytes):
        self.blob = maybe_decompress(blob)
        self._members = _zip_members(self.blob)
        self._arrays: dict[str, _Array] = {}
        self.meta = json.loads(self.read([_META_KEY])[_META_KEY].tobytes().decode())

    @property
    def files(self) -> tuple[str, ...]:
        """Every member's key, in the blob's order."""
        return tuple(self._members)

    def contents(self) -> tuple[dict, list[str], dict[str, str]]:
        """A copy of ``meta`` without the envelope's own entries, the leaf
        keys in ``__order__`` and the ``__dtypes__`` they restore to."""
        meta = dict(self.meta)
        return meta, meta.pop("__order__"), meta.pop("__dtypes__", {})

    def _array(self, key: str) -> _Array:
        a = self._arrays.get(key)
        if a is not None:
            return a
        m = self._members[key]
        if m.deflated:
            buf, start, stop = _inflate(self.blob, m, key), 0, m.size
        else:
            buf, start, stop = self.blob, m.start, m.stop
        preamble = io.BytesIO(buf[start:min(stop, start + _NPY_PREAMBLE_MAX)])
        try:
            if npy_format.read_magic(preamble) != (1, 0):
                raise ValueError("a .npy header past format 1.0")
            shape, fortran_order, dtype = npy_format.read_array_header_1_0(preamble)
        except ValueError as e:
            raise ValueError(f"member {key!r}: {e}") from None
        offset = start + preamble.tell()
        if dtype.kind not in _DTYPE_KINDS or offset + int(np.prod(shape)) * dtype.itemsize != stop:
            raise ValueError(f"member {key!r}: a {dtype} array of shape {shape} "
                             f"in {stop - offset} bytes")
        a = self._arrays[key] = _Array(buf, offset, dtype, tuple(shape),
                                       "F" if fortran_order else "C")
        return a

    def view(self, key: str) -> np.ndarray:
        """A read-only view of member ``key``'s array, checked against its
        CRC only if the member was deflated."""
        a = self._array(key)
        return np.ndarray(a.shape, a.dtype, a.buf, a.offset, order=a.order)

    def _check(self, key: str) -> None:
        m = self._members[key]
        if m.deflated:
            self._array(key)  # inflating it checked it
        elif zlib.crc32(memoryview(self.blob)[m.start:m.stop]) != m.crc:
            raise zipfile.BadZipFile(f"Bad CRC-32 for file {key}.npy")

    def copy_checked(self, jobs, keys) -> None:
        """Run ``jobs`` (dst, src view, scale) and check the CRC of every
        member in ``keys``; big pieces go to the codec pool (``_run_tasks``)."""
        tasks = [(self._members[k].stop - self._members[k].start, self._check, (k,))
                 for k in keys]
        for dst, src, scale in jobs:
            step = max(1, _PIECE_BYTES // dst.itemsize)
            for i in range(0, dst.size, step):
                tasks.append((min(step, dst.size - i) * dst.itemsize, _copy_leaf,
                              (dst[i:i + step], src[i:i + step], scale)))
        _run_tasks(tasks)

    def read(self, keys) -> dict[str, np.ndarray]:
        """Members ``keys`` as checked, writable arrays of their own: what
        ``np.load`` gives for them."""
        out, jobs = {}, []
        for key in keys:
            src = self.view(key)
            dst = out[key] = np.empty_like(src)
            jobs.append((dst.ravel("K"), src.ravel("K"), None))
        self.copy_checked(jobs, out)
        return out


def _run_tasks(tasks: list) -> list:
    """Run ``tasks`` (bytes, fn, args), biggest first: those of
    ``_INLINE_BYTES`` or more on the codec pool, the rest on the caller's
    thread. Returns their results in order. A failure is raised once every
    task has finished, so nothing writes into a destination after the call
    returns."""
    order = sorted(range(len(tasks)), key=lambda i: -tasks[i][0])
    pool = _codec_pool()
    futures = {i: pool.submit(tasks[i][1], *tasks[i][2]) for i in order
               if tasks[i][0] >= _INLINE_BYTES}
    results = [None] * len(tasks)
    try:
        for i in order:
            if i not in futures:
                results[i] = tasks[i][1](*tasks[i][2])
    finally:
        wait(futures.values())
    for i, f in futures.items():
        results[i] = f.result()
    return results


# --- the writer: arrays → the bytes np.savez would give ------------------------
#
# The write side's mirror of ``NpzEnvelope``, and the only writer of the
# envelope. ``np.savez`` hands each array to ``zipfile`` in 16 MiB ``tobytes``
# chunks, which it CRCs and appends to a growing ``BytesIO``, all on one
# thread, and a bf16 leaf must first be widened into an f32 temporary. Here
# each member is laid out first (the zip64 local header ``np.savez`` forces,
# the ``.npy`` 1.0 header ``numpy.lib.format`` writes, the payload). A stored
# envelope is then one bytes object of the blob's final size filled in one
# pass: each leaf cast straight into its payload slot and CRC'd as it is
# written, big leaves in pieces on the codec pool; the local headers, which
# carry the CRCs, and the central directory come last. A deflated envelope
# ('npz') deflates each member whole, members in parallel on the pool, with
# the raw deflate ``zipfile`` uses at its default level: zlib's output does
# not depend on how its input is cut, so the bytes are
# ``np.savez_compressed``'s. ``ZipFile.open(name, "w")`` stamps every member
# 1980-01-01 00:00, so the bytes do not depend on the clock. Sizes and
# offsets past ``zipfile.ZIP64_LIMIT`` (2 GiB) go in zip64 fields, as
# ``zipfile`` writes them. An object array (``np.savez`` would pickle it) or a
# structured one raises ``ValueError`` naming its key: no caller makes one, and
# ``np.load`` could not read a pickled one back anyway.


def _crc_multmodp(a: int, b: int) -> int:
    """a·b modulo the CRC-32 polynomial, bit-reflected (zlib's multmodp);
    ``a`` is nonzero."""
    m, p = 1 << 31, 0
    while True:
        if a & m:
            p ^= b
            if not a & (m - 1):
                return p
        m >>= 1
        b = (b >> 1) ^ 0xEDB88320 if b & 1 else b >> 1


_CRC_X2N = [1 << 30]  # x^(2^k) modulo the polynomial, k = 0..31
for _ in range(31):
    _CRC_X2N.append(_crc_multmodp(_CRC_X2N[-1], _CRC_X2N[-1]))


@functools.lru_cache(maxsize=64)
def _crc_shift(nbytes: int) -> int:
    """x^(8·nbytes) modulo the polynomial: what appending ``nbytes`` bytes
    does to a CRC."""
    p, k = 1 << 31, 3
    while nbytes:
        if nbytes & 1:
            p = _crc_multmodp(_CRC_X2N[k & 31], p)
        nbytes >>= 1
        k += 1
    return p


def _crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """CRC-32 of A + B from crc32(A), crc32(B) and len(B) (zlib's
    crc32_combine), so pieces of one member can be CRC'd apart."""
    return _crc_multmodp(_crc_shift(len2), crc1) ^ crc2


class _Storage:
    """A bytes object's storage as a writable array interface; holds the
    object, so views of it keep it alive."""

    def __init__(self, blob: bytes):
        self.blob = blob
        addr = np.frombuffer(blob, np.uint8).__array_interface__["data"][0]
        self.__array_interface__ = {"data": (addr, False), "shape": (len(blob),),
                                    "typestr": "|u1", "version": 3}


def _unfilled_bytes(nbytes: int) -> tuple[bytes, np.ndarray]:
    """A new bytes object of ``nbytes`` (nonzero) and a writable uint8
    view of its storage, which the caller fills before anyone else sees the
    object. ``bytes(n)`` comes from calloc, so a big one is fresh pages no
    thread has touched yet: each is first touched by the thread that fills
    it, where a ``BytesIO`` grown to size zeroes the whole blob on one."""
    blob = bytes(nbytes)
    return blob, np.asarray(_Storage(blob))


def _stored_payload(key: str, arr: np.ndarray) -> tuple[np.ndarray, np.dtype, bool]:
    """``arr`` as ``np.savez`` stores it: its payload in file order as a 1-D
    view, the dtype it ships as, and the ``.npy`` header's fortran_order."""
    wire, restored = _wire_dtype(arr.dtype)
    if (arr.dtype.fields is not None or not wire.itemsize
            or not (restored or arr.dtype.kind in _DTYPE_KINDS)):
        raise ValueError(f"member {key!r}: a {arr.dtype} array has no place in the envelope")
    if arr.flags.c_contiguous:
        return arr.reshape(-1), wire, False
    if arr.flags.f_contiguous:
        return arr.T.reshape(-1), wire, True
    # np.savez writes a strided array in C order; a widened one is the f32
    # copy ``astype`` makes, which keeps the array's own order
    return _stored_payload(key, arr.astype(wire) if restored else np.ascontiguousarray(arr))


def _fill_piece(dst: np.ndarray, src: np.ndarray, written: np.ndarray) -> int:
    """Cast ``src`` into ``dst`` and return the CRC-32 of ``written``, the
    bytes ``dst`` covers."""
    np.copyto(dst, src, casting="unsafe")
    return zlib.crc32(written)


def _deflate_member(head: bytes, src: np.ndarray, wire: np.dtype) -> tuple[int, bytes]:
    """The CRC-32 of a .npy file (``head`` then ``src`` as ``wire``) and the
    file deflated as ``zipfile`` deflates it."""
    payload = np.asarray(src, wire).view(np.uint8)
    deflate = zlib.compressobj(zlib.Z_DEFAULT_COMPRESSION, zlib.DEFLATED, -15)
    data = deflate.compress(head) + deflate.compress(payload) + deflate.flush()
    return zlib.crc32(payload, zlib.crc32(head)), data


def _encoded_name(info: zipfile.ZipInfo) -> tuple[bytes, int]:
    """The member name's bytes and flag bits, as ``zipfile`` encodes them."""
    try:
        return info.filename.encode("ascii"), info.flag_bits
    except UnicodeEncodeError:
        return info.filename.encode("utf-8"), info.flag_bits | 0x800


def _zip_directory(infos: list[zipfile.ZipInfo], start: int) -> bytes:
    """The central directory and end record ``zipfile`` writes after members
    ``infos``, the directory starting at byte ``start`` of the blob."""
    limit, parts = zipfile.ZIP64_LIMIT, []
    for zi in infos:
        size, csize, offset, wide = zi.file_size, zi.compress_size, zi.header_offset, []
        if size > limit or csize > limit:
            wide += [size, csize]
            size = csize = 0xFFFFFFFF
        if offset > limit:
            wide.append(offset)
            offset = 0xFFFFFFFF
        extra = struct.pack(f"<HH{len(wide)}Q", 1, 8 * len(wide), *wide) if wide else b""
        name, flags = _encoded_name(zi)
        y, mo, d, h, mi, sec = zi.date_time
        parts += [struct.pack(
            zipfile.structCentralDir, zipfile.stringCentralDir, zi.create_version,
            zi.create_system, zi.extract_version, zi.reserved, flags, zi.compress_type,
            h << 11 | mi << 5 | sec // 2, (y - 1980) << 9 | mo << 5 | d, zi.CRC,
            csize, size, len(name), len(extra), 0, 0,
            zi.internal_attr, zi.external_attr, offset), name, extra]
    count, size = len(infos), sum(map(len, parts))
    if count > zipfile.ZIP_FILECOUNT_LIMIT or start > limit or size > limit:
        parts.append(struct.pack(
            zipfile.structEndArchive64, zipfile.stringEndArchive64,
            zipfile.sizeEndCentDir64 - 12, 45, 45, 0, 0, count, count, size, start))
        parts.append(struct.pack(zipfile.structEndArchive64Locator,
                                 zipfile.stringEndArchive64Locator, 0, start + size, 1))
        count, size, start = min(count, 0xFFFF), min(size, 0xFFFFFFFF), min(start, 0xFFFFFFFF)
    parts.append(struct.pack(zipfile.structEndArchive, zipfile.stringEndArchive,
                             0, 0, count, count, size, start, 0))
    return b"".join(parts)


class _Slot(NamedTuple):
    """One member of an npz being written."""

    info: zipfile.ZipInfo
    head: bytes         # the .npy header
    src: np.ndarray     # the payload in file order, in the array's own dtype
    wire: np.dtype      # the dtype it ships as


def _write_stored(arrays: dict[str, np.ndarray], *, deflate: bool = False) -> bytes:
    """The npz ``np.savez`` writes for ``arrays`` (with ``deflate``, the one
    ``np.savez_compressed`` writes), each array shipped as ``_wire_dtype``
    says."""
    slots = []
    for key, arr in arrays.items():
        arr = np.asarray(arr)
        src, wire, fortran = _stored_payload(key, arr)
        head = io.BytesIO()
        npy_format.write_array_header_1_0(head, {
            "descr": npy_format.dtype_to_descr(wire), "fortran_order": fortran,
            "shape": arr.shape})
        info = zipfile.ZipInfo(key + ".npy")
        info.external_attr, info.CRC = 0o600 << 16, 0
        info.file_size = head.tell() + src.size * wire.itemsize
        if deflate:
            info.compress_type = zipfile.ZIP_DEFLATED
        slots.append(_Slot(info, head.getvalue(), src, wire))
    infos = [s.info for s in slots]
    if deflate:
        members = _run_tasks([(s.src.size * s.wire.itemsize, _deflate_member,
                               (s.head, s.src, s.wire)) for s in slots])
        parts, pos = [], 0
        for s, (crc, data) in zip(slots, members):
            s.info.CRC, s.info.compress_size, s.info.header_offset = crc, len(data), pos
            local = s.info.FileHeader(True)
            parts += [local, data]
            pos += len(local) + len(data)
        return b"".join(parts + [_zip_directory(infos, pos)])
    starts, pos = [], 0
    for s in slots:
        s.info.compress_size, s.info.header_offset = s.info.file_size, pos
        starts.append(pos + len(s.info.FileHeader(True)))
        pos = starts[-1] + s.info.file_size
    blob, out = _unfilled_bytes(pos + len(_zip_directory(infos, pos)))
    tasks, pieces = [], []  # pieces: per member, (task index, bytes) in file order
    for s, start in zip(slots, starts):
        out[start:start + len(s.head)] = np.frombuffer(s.head, np.uint8)
        written = out[start + len(s.head):start + s.info.file_size]
        dst, size = written.view(s.wire), s.wire.itemsize
        step = max(1, _PIECE_BYTES // size)
        pieces.append([])
        for j in range(0, s.src.size, step):
            n = min(step, s.src.size - j) * size
            pieces[-1].append((len(tasks), n))
            tasks.append((n, _fill_piece, (dst[j:j + step], s.src[j:j + step],
                                           written[j * size:j * size + n])))
    crcs = _run_tasks(tasks)
    for s, start, member in zip(slots, starts, pieces):
        crc = zlib.crc32(s.head)
        for t, n in member:
            crc = _crc32_combine(crc, crcs[t], n)
        s.info.CRC = crc
        out[s.info.header_offset:start] = np.frombuffer(s.info.FileHeader(True), np.uint8)
    out[pos:] = np.frombuffer(_zip_directory(infos, pos), np.uint8)
    return blob


def _restored_dtype(name: str) -> np.dtype:
    import ml_dtypes

    return np.dtype(getattr(ml_dtypes, name))


def _spec_table_key(order, dtypes, quantized, wire_dtypes=()) -> tuple:
    """Structure identity for spec interning. ``wire_dtypes`` (the native
    npz array dtypes, in leaf order) must participate: same-structure f16 and
    f32 models are indistinguishable by order + ``__dtypes__`` alone (that
    map only records ml_dtypes restores), and sharing one spec across them
    would silently retype leaves on unflatten."""
    return ("q" if quantized else "f", tuple(order),
            tuple(sorted(dtypes.items())), tuple(wire_dtypes))


def _build_wire_spec(order, dtypes, shapes_by_key, quantized) -> LeafSpec:
    """LeafSpec for a wire structure, in the *canonical* (rebuilt-dict flatten)
    leaf order — identical to the order a tree-path reader's pytree would
    flatten to, so flat and tree readers agree on layout byte-for-byte."""
    skeleton = _rebuild_tree(list(shapes_by_key), {}, lambda key: 0)
    canon_paths, treedef = jax.tree_util.tree_flatten_with_path(skeleton)
    paths, shapes, dts = [], [], []
    for path, _ in canon_paths:
        p = path_str(path)
        key = p.replace("/", _SEP)
        paths.append(p)
        shapes.append(shapes_by_key[key][0])
        if quantized:
            dts.append(np.dtype(np.float32))  # dequantized leaves are f32
        elif key in dtypes:
            dts.append(_restored_dtype(dtypes[key]))
        else:
            dts.append(shapes_by_key[key][1])
    return LeafSpec(paths, shapes, dts, treedef)


def _wire_keys(spec: LeafSpec) -> tuple[str, ...]:
    """Spec paths in wire ('|'-separated) form, cached on the spec object."""
    keys = getattr(spec, "_wire_keys", None)
    if keys is None:
        keys = tuple(p.replace("/", _SEP) for p in spec.paths)
        spec._wire_keys = keys
    return keys


def decode_params_flat(blob: "bytes | NpzEnvelope",
                       specs: dict) -> tuple[LeafSpec, np.ndarray, dict]:
    """Decode a full or quantized npz blob straight into one preallocated flat
    f32 vector — no nested-dict rebuild. ``blob`` may be the bytes or their
    already-parsed ``NpzEnvelope``. ``specs`` is a caller-owned interning
    table (structure key → LeafSpec); pass the same dict across calls so all
    updates of one model share a spec. Raises ``FlatDecodeUnsupported`` for
    blobs whose wire arrays don't embed losslessly in f32, and
    ``zipfile.BadZipFile`` for a torn or corrupted blob."""
    env = blob if isinstance(blob, NpzEnvelope) else NpzEnvelope(blob)
    meta, order, dtypes = env.contents()
    if "delta_of" in meta:
        raise ValueError("delta blob: use deserialize_update_delta_flat")
    quantized = bool(meta.get("quantized"))
    if quantized:
        # order lists the packed {"q":..., "s":...} tree; the spec
        # describes the original structure (q-keys with prefix stripped)
        leaf_keys = [k[2:] for k in order if k.startswith("q" + _SEP)]
        arrays = {k: env.view("q" + _SEP + k) for k in leaf_keys}
        scales = {k: np.float32(env.view("s" + _SEP + k)) for k in leaf_keys}
        read = [p + _SEP + k for k in leaf_keys for p in "qs"]
    else:
        leaf_keys = list(order)
        arrays = {k: env.view(k) for k in leaf_keys}
        scales = {}
        read = leaf_keys
        for k, a in arrays.items():
            if a.dtype.kind != "f" or a.dtype.itemsize > 4:
                raise FlatDecodeUnsupported(
                    f"leaf {k!r} has wire dtype {a.dtype} (not f32-exact)")
    wire_dtypes = () if quantized else tuple(arrays[k].dtype.str for k in leaf_keys)
    skey = _spec_table_key(order, dtypes, quantized, wire_dtypes)
    spec = specs.get(skey)
    drifted = False
    if spec is not None:
        # verify shapes still match the interned layout; drift → rebuild
        # (dtypes are part of the table key, so only shapes can drift)
        wire = _wire_keys(spec)
        if len(wire) != len(leaf_keys) or any(
            tuple(arrays[k].shape) != spec.shapes[spec.index[k.replace(_SEP, "/")]]
            for k in leaf_keys
        ):
            spec, drifted = None, True
    if spec is None:
        shapes_by_key = {k: (tuple(a.shape), a.dtype) for k, a in arrays.items()}
        spec = _build_wire_spec(order, dtypes, shapes_by_key, quantized)
        if drifted:
            specs[skey] = spec  # replace the stale layout
        else:
            # setdefault: a concurrent decode (the prefetch thread racing
            # a pull) must not intern two spec instances for one structure
            # — spec identity is what makes the stack cache zero-copy
            spec = specs.setdefault(skey, spec)
    flat = spec.empty_flat()
    index, offsets, sizes = spec.index, spec.offsets, spec.sizes
    jobs = []
    for k in leaf_keys:
        i = index[k.replace(_SEP, "/")]
        o, n = offsets[i], sizes[i]
        jobs.append((flat[o:o + n], arrays[k].reshape(-1), scales.get(k)))
    env.copy_checked(jobs, read)
    return spec, flat, meta


def flat_update_from_meta(spec: LeafSpec, flat: np.ndarray,
                          meta: dict[str, Any]) -> FlatUpdate:
    return FlatUpdate(
        flat, spec,
        num_examples=int(meta["num_examples"]),
        node_id=str(meta["node_id"]),
        counter=int(meta["counter"]),
        timestamp=float(meta["timestamp"]),
        metrics=meta.get("metrics", {}),
        lease_epoch=int(meta.get("lease_epoch", 0)),
    )


def deserialize_update_flat(blob: bytes, specs: dict) -> FlatUpdate:
    """Full/quantized blob → FlatUpdate (see ``decode_params_flat``)."""
    spec, flat, meta = decode_params_flat(blob, specs)
    return flat_update_from_meta(spec, flat, meta)


def canonicalize_params(params: PyTree) -> PyTree:
    """The nested-dict tree a reader reconstructs after a serialize round-trip
    (wire dtype conversion included), computed without the npz I/O. A delta
    writer diffs future updates against this so its view of the base is
    bitwise-identical to every reader's."""
    wire = _flat_wire(params)
    dtypes = {k: dt for k, (_, dt) in wire.items() if dt}
    return _rebuild_tree(list(wire), dtypes, lambda key: np.array(wire[key][0], copy=True))


def _update_meta(update: NodeUpdate, **extra: Any) -> dict[str, Any]:
    return {
        "num_examples": int(update.num_examples),
        "node_id": update.node_id,
        "counter": int(update.counter),
        "timestamp": float(update.timestamp),
        "metrics": update.metrics,
        "lease_epoch": int(getattr(update, "lease_epoch", 0)),
        **extra,
    }


def _update_from_meta(params: PyTree, meta: dict[str, Any]) -> NodeUpdate:
    return NodeUpdate(
        params=params,
        num_examples=int(meta["num_examples"]),
        node_id=str(meta["node_id"]),
        counter=int(meta["counter"]),
        timestamp=float(meta["timestamp"]),
        metrics=meta.get("metrics", {}),
        lease_epoch=int(meta.get("lease_epoch", 0)),
    )


def serialize_update(update: NodeUpdate, *, compress: str = "none") -> bytes:
    return serialize_params(update.params, meta=_update_meta(update), compress=compress)


def deserialize_update(blob: bytes) -> NodeUpdate:
    params, meta = deserialize_params(blob)
    return _update_from_meta(params, meta)


def content_hash(blob: bytes) -> str:
    return hashlib.sha256(blob).hexdigest()[:16]


def peek_meta(blob: bytes) -> dict[str, Any]:
    """Read only the ``__meta__`` entry of a serialized blob (cheap dispatch:
    full vs quantized vs delta) without materializing the weight arrays."""
    return NpzEnvelope(blob).meta


# --- group summaries (sharded gossip store) ---------------------------------
#
# A group's deposit in the gossip layer: the example-weighted mean of the
# group's latest params plus enough metadata for receivers to (a) weight it
# like a pseudo-peer in client-side aggregation (``num_examples`` = the total
# behind the mean) and (b) order competing copies by freshness. The blob rides
# the same self-describing npz envelope as every other deposit — ``peek_meta``
# dispatches on ``summary_of`` exactly like it does on ``delta_of`` /
# ``quantized`` — so heterogeneous readers never need out-of-band schema.


@dataclass
class GroupSummary:
    """One group's aggregate deposit in the gossip layer."""

    params: PyTree              # example-weighted mean of the group's latest params
    num_examples: int           # total examples behind that mean
    origin: int                 # group index that produced the summary
    version: int                # monotone freshness scalar: sum of (counter + 1)
    version_vector: dict        # node_id -> latest counter folded into the mean
    timestamp: float = 0.0      # newest member timestamp (staleness strategies)


def serialize_group_summary(summary: GroupSummary, *, compress: str = "none") -> bytes:
    return serialize_params(
        summary.params,
        compress=compress,
        meta={
            "summary_of": int(summary.origin),
            "num_examples": int(summary.num_examples),
            "version": int(summary.version),
            "version_vector": {str(k): int(v) for k, v in summary.version_vector.items()},
            "timestamp": float(summary.timestamp),
        },
    )


def deserialize_group_summary(blob: bytes) -> GroupSummary:
    params, meta = deserialize_params(blob)
    if "summary_of" not in meta:
        raise ValueError("not a group-summary blob")
    return GroupSummary(
        params=params,
        num_examples=int(meta["num_examples"]),
        origin=int(meta["summary_of"]),
        version=int(meta["version"]),
        version_vector={str(k): int(v) for k, v in meta["version_vector"].items()},
        timestamp=float(meta.get("timestamp", 0.0)),
    )


# --- super-summaries (hierarchical gossip tiers) ------------------------------
#
# A level-k aggregator's fold of one ring segment: the example-weighted mean of
# the segment's child summaries, plus per-child freshness (``child_versions``)
# so staleness is detectable per level without decoding. The version vector
# carries per-child counter *maxima* (keyed by the child's pseudo-peer id),
# not a fleet-wide node vector: the propagated counter — what ``FedAsync``
# discounting compares against its own epoch — stays exact through arbitrarily
# many tiers while blob metadata stays O(branching), and the true per-node
# vector remains one level-0 hop away. Dispatches on ``super_summary_of`` like
# every other wire family.


@dataclass
class SuperSummary:
    """One ring segment's folded deposit at tier ``level`` of the summary tree."""

    params: PyTree              # example-weighted mean of the child summaries
    num_examples: int           # total examples behind that mean
    origin: int                 # segment index at this level
    level: int                  # tier (>= 1; level-0 deposits are GroupSummary)
    version: int                # sum of the child version scalars (monotone)
    child_versions: dict        # child origin key -> version scalar folded in
    version_vector: dict        # child pseudo-peer id -> its counter maximum
    timestamp: float = 0.0      # newest child timestamp


def serialize_super_summary(summary: SuperSummary, *, compress: str = "none") -> bytes:
    return serialize_params(
        summary.params,
        compress=compress,
        meta={
            "super_summary_of": int(summary.origin),
            "level": int(summary.level),
            "num_examples": int(summary.num_examples),
            "version": int(summary.version),
            "child_versions": {str(k): int(v) for k, v in summary.child_versions.items()},
            "version_vector": {str(k): int(v) for k, v in summary.version_vector.items()},
            "timestamp": float(summary.timestamp),
        },
    )


def deserialize_super_summary(blob: bytes) -> SuperSummary:
    params, meta = deserialize_params(blob)
    if "super_summary_of" not in meta:
        raise ValueError("not a super-summary blob")
    return SuperSummary(
        params=params,
        num_examples=int(meta["num_examples"]),
        origin=int(meta["super_summary_of"]),
        level=int(meta["level"]),
        version=int(meta["version"]),
        child_versions={str(k): int(v) for k, v in meta["child_versions"].items()},
        version_vector={str(k): int(v) for k, v in meta["version_vector"].items()},
        timestamp=float(meta.get("timestamp", 0.0)),
    )


# --- strategy-state recovery blobs -------------------------------------------
#
# A node's optimizer state (FedAvgM momentum, FedAdam/FedYogi/FedAdagrad
# moments) lives client-side; a crashed-and-restarted node that recovers its
# params from ``latest/`` but restarts its strategy cold loses the server-
# optimizer trajectory. These blobs persist the flat state vectors under
# ``state/<node>`` — the same self-describing npz envelope as every other
# deposit (``peek_meta`` dispatches on ``state_of``), riding the pipeline's
# compressed envelope.


def serialize_strategy_state(node_id: str, strategy: str, counter: int,
                             state: dict[str, np.ndarray], *,
                             compress: str = "none") -> bytes:
    return serialize_params(
        {k: np.asarray(v) for k, v in state.items()},
        compress=compress,
        meta={"state_of": node_id, "strategy": strategy,
              "counter": int(counter)},
    )


def deserialize_strategy_state(blob: bytes) -> tuple[dict, dict]:
    """-> (state arrays by name, meta with state_of/strategy/counter)."""
    state, meta = deserialize_params(blob)
    if "state_of" not in meta:
        raise ValueError("not a strategy-state blob")
    return state, meta


# --- fleet-control blobs (launcher / chaos-soak harness) ---------------------
#
# The multi-host fleet launcher (``repro.fleet``) coordinates through the
# shared folder itself — spec, slot claims, heartbeats, per-node results and
# per-worker reports are all just deposits, so there is no coordinator in the
# data path. They ride the same self-describing npz envelope as every other
# blob (``peek_meta`` dispatches on ``fleet_of`` exactly like ``summary_of`` /
# ``state_of`` / ``delta_of``); the payload is pure JSON metadata, no arrays.
# Every fleet key lives under the ``fleet/`` prefix, which the stores exclude
# from state hashes: a heartbeat must never look like federation signal and
# trigger a fleet-wide re-pull.


def serialize_fleet_blob(kind: str, payload: dict, *, compress: str = "none") -> bytes:
    """One fleet-control deposit: ``kind`` ∈ {spec, claim, heartbeat, result,
    worker, ...} plus a JSON-serializable payload."""
    return serialize_params(
        {}, compress=compress,
        meta={"fleet_of": str(kind), "payload": dict(payload)},
    )


def deserialize_fleet_blob(blob: bytes) -> tuple[str, dict]:
    """-> (kind, payload). Raises ValueError on non-fleet blobs."""
    _params, meta = deserialize_params(blob)
    if "fleet_of" not in meta:
        raise ValueError("not a fleet-control blob")
    return str(meta["fleet_of"]), dict(meta.get("payload") or {})


# --- observability blobs (store-native telemetry plane) ----------------------
#
# Telemetry snapshots ride the store as their own family under ``obs/<node>/
# <seq>`` — the serverless answer to "where does a round's time go": there is
# no metrics server, so per-node phase latencies, staleness distributions and
# wire counters are deposited as blobs and assembled read-only by any peer
# (``python -m repro.obs``). Same envelope, same exclusion rule as ``fleet/``:
# an obs deposit must never perturb ``state_hash`` and trigger re-pulls.


def serialize_obs_blob(node_id: str, seq: int, payload: dict, *,
                       compress: str = "none") -> bytes:
    """One telemetry snapshot deposit for ``obs/<node_id>/<seq>``."""
    return serialize_params(
        {}, compress=compress,
        meta={"obs_of": str(node_id), "seq": int(seq),
              "payload": dict(payload)},
    )


def deserialize_obs_blob(blob: bytes) -> tuple[str, int, dict]:
    """-> (node_id, seq, payload). Raises ValueError on non-obs blobs."""
    _params, meta = deserialize_params(blob)
    if "obs_of" not in meta:
        raise ValueError("not a telemetry blob")
    return (str(meta["obs_of"]), int(meta.get("seq", 0)),
            dict(meta.get("payload") or {}))


# --- int8 compressed payloads (beyond-paper extension #4) -------------------


def quantize_leaf(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-tensor int8 quantization: returns (q, scale)."""
    x = np.asarray(x, np.float32)
    scale = np.maximum(np.abs(x).max(), 1e-12) / 127.0
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    return q, np.float32(scale)


def dequantize_leaf(q: np.ndarray, scale: np.ndarray) -> np.ndarray:
    return q.astype(np.float32) * np.float32(scale)


def serialize_update_quantized(update: NodeUpdate, *, compress: str = "none") -> bytes:
    qtree = jax.tree.map(lambda x: quantize_leaf(np.asarray(x))[0], update.params)
    stree = jax.tree.map(lambda x: quantize_leaf(np.asarray(x))[1], update.params)
    return serialize_params(
        {"q": qtree, "s": stree}, meta=_update_meta(update, quantized=True),
        compress=compress
    )


def deserialize_update_quantized(blob: bytes) -> NodeUpdate:
    packed, meta = deserialize_params(blob)
    params = jax.tree.map(dequantize_leaf, packed["q"], packed["s"])
    return _update_from_meta(params, meta)


# --- delta payloads against a content-hashed base ---------------------------
#
# Transport fast path for the weight store: after the first full deposit, a
# node ships only the entries that changed relative to a *base* blob it also
# deposited (content-addressed, so readers can verify they reconstruct against
# the exact bytes the writer diffed against). The sparse encoding stores the
# NEW values at changed positions — not arithmetic differences — so
# reconstruction is bitwise-exact and aggregation over reconstructed params
# equals aggregation over full blobs exactly.

_DENSE = "d" + _SEP  # per-leaf dense fallback
_IDX = "i" + _SEP    # changed flat indices
_VAL = "v" + _SEP    # new values at those indices
_SCALE = "c" + _SEP  # int8 scale when the delta values are quantized


class DeltaBaseMismatch(RuntimeError):
    """The base blob a delta references is missing or has different content."""


def _flat_wire(params: PyTree) -> dict[str, tuple[np.ndarray, str | None]]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        out[path_str(path).replace("/", _SEP)] = _wire_leaf(leaf)
    return out


def delta_density(params: PyTree, base_params: PyTree) -> float:
    """Fraction of entries that differ from the base (1.0 on any structural
    mismatch). Diagnostic helper for sizing experiments — the store itself
    decides delta-vs-rebase by comparing the encoded delta's size against the
    tree's raw byte size, which costs only the one serialization scan."""
    new, base = _flat_wire(params), _flat_wire(base_params)
    if set(new) != set(base):
        return 1.0
    changed = total = 0
    for key, (a, _) in new.items():
        b = base[key][0]
        if a.shape != b.shape or a.dtype != b.dtype:
            return 1.0
        total += a.size
        changed += int(np.count_nonzero(a.reshape(-1) != b.reshape(-1)))
    return changed / max(total, 1)


def apply_update_delta_flat(blob: bytes, spec: LeafSpec,
                            flat: np.ndarray) -> dict[str, Any]:
    """Apply a delta blob's sparse entries *in place* on ``flat`` (which must
    already hold the referenced base state); returns the blob's meta. The
    in-place form is what lets a chain walk reconstruct K links with one base
    copy instead of K. On a raised exception ``flat`` may be partially
    mutated — callers discard it (the exceptions signal a structure/dtype
    mismatch, never a transient)."""
    env = NpzEnvelope(blob)
    meta, order, _dtypes = env.contents()
    if "delta_of" not in meta:
        raise ValueError("not a delta blob")
    wire = _wire_keys(spec)
    if len(order) != len(wire) or set(order) != set(wire):
        raise ValueError("delta structure does not match the base spec")
    data = env.read(env.files)
    index, offsets, sizes = spec.index, spec.offsets, spec.sizes
    for key in order:
        i = index[key.replace(_SEP, "/")]
        o, n = offsets[i], sizes[i]
        if _DENSE + key in data:
            arr = data[_DENSE + key]
            if arr.size != n:
                raise ValueError(f"dense leaf {key!r}: {arr.size} vs {n}")
            if _SCALE + key in data:  # dense int8-quantized leaf
                flat[o:o + n] = dequantize_leaf(arr.reshape(-1), data[_SCALE + key])
                continue
            if arr.dtype.kind != "f" or arr.dtype.itemsize > 4:
                raise FlatDecodeUnsupported(
                    f"leaf {key!r} has wire dtype {arr.dtype} (not f32-exact)")
            flat[o:o + n] = arr.reshape(-1)
            continue
        idx = data[_IDX + key]
        vals = data[_VAL + key]
        if _SCALE + key in data:
            vals = dequantize_leaf(vals, data[_SCALE + key])
        elif vals.size and (vals.dtype.kind != "f" or vals.dtype.itemsize > 4):
            raise FlatDecodeUnsupported(
                f"leaf {key!r} delta values have wire dtype {vals.dtype}")
        flat[o + idx] = vals
    return meta


def deserialize_update_delta_flat(blob: bytes, spec: LeafSpec,
                                  base_flat: np.ndarray) -> FlatUpdate:
    """Reconstruct a FlatUpdate from a delta blob by applying its sparse
    entries in place on a copy of the *flat* base vector — no nested-dict
    rebuild, no per-leaf tree traversal. Raises ValueError when the blob's
    structure does not match ``spec`` (caller falls back to the tree path)."""
    flat = np.array(base_flat, dtype=np.float32, copy=True)
    meta = apply_update_delta_flat(blob, spec, flat)
    return flat_update_from_meta(spec, flat, meta)


def serialize_update_delta_from_flat(
    update: NodeUpdate,
    spec: LeafSpec,
    flat: np.ndarray,
    base_flat: np.ndarray,
    base_hash: str,
    *,
    changed: np.ndarray | None = None,
    density_threshold: float = 0.5,
    compress: str = "none",
    quantize_leaves: "frozenset[int] | set[int] | tuple[int, ...]" = (),
    extra_meta: dict[str, Any] | None = None,
) -> bytes:
    """Encode ``flat`` as a sparse per-leaf diff against ``base_flat`` — the
    exact wire format of ``serialize_update_delta``, so any reader reconstructs
    it with zero knowledge of how the writer chose the changed set (this is
    what makes writer-side top-k/error-feedback policies transparent).
    ``changed`` (sorted flat indices that differ from the base) may be passed
    when the caller already computed it; ``quantize_leaves`` names leaf
    indices whose changed values ship int8-quantized (per-segment scale under
    the ``c|`` key, lossy — the family codec's ``quantized`` sub-policy);
    ``extra_meta`` adds writer-side meta keys (e.g. the chain codec's
    ``chain_depth``). Vectorized: the only per-leaf work is emitting npz
    entries, which the wire format requires anyway."""
    flat = np.asarray(flat, np.float32).reshape(-1)
    if flat.size != spec.num_params:
        raise ValueError(f"{flat.size} params vs spec's {spec.num_params}")
    if changed is None:
        changed = np.flatnonzero(flat != np.asarray(base_flat).reshape(-1))
    arrays: dict[str, np.ndarray] = {}
    order: list[str] = []
    dtypes: dict[str, str] = {}
    keys = _wire_keys(spec)
    # one vectorized split of the changed set into per-leaf segments
    cuts = np.searchsorted(changed, spec.bounds)
    for i, key in enumerate(keys):
        order.append(key)
        dt = spec.dtypes[i]
        wire_dt, restored = _wire_dtype(np.dtype(dt))
        if restored:
            dtypes[key] = restored
        o, n = spec.offsets[i], spec.sizes[i]
        seg = changed[cuts[i]:cuts[i + 1]]
        if i in quantize_leaves and seg.size:
            if seg.size > density_threshold * n:
                # dense quantized: int8 leaf + per-leaf scale (a d|-plus-c|
                # pair, which readers dequantize) — 1 byte/entry where the
                # sparse form would pay 5 (int32 index + int8 value)
                q, scale = quantize_leaf(flat[o:o + n])
                arrays[_DENSE + key] = q.reshape(spec.shapes[i])
                arrays[_SCALE + key] = np.asarray(scale)
                continue
            arrays[_IDX + key] = (seg - o).astype(
                np.int64 if n > 2**31 else np.int32)
            q, scale = quantize_leaf(flat[seg])
            arrays[_VAL + key] = q
            arrays[_SCALE + key] = np.asarray(scale)
            continue
        if seg.size > density_threshold * n:
            arrays[_DENSE + key] = np.asarray(
                flat[o:o + n], dtype=wire_dt).reshape(spec.shapes[i])
            continue
        idx = (seg - o).astype(np.int64 if n > 2**31 else np.int32)
        arrays[_IDX + key] = idx
        arrays[_VAL + key] = np.asarray(flat[seg], dtype=wire_dt)
    meta = _update_meta(update, delta_of=base_hash, **(extra_meta or {}))
    return _pack_npz(arrays, order, dtypes, meta, compress=compress)


def serialize_update_delta(
    update: NodeUpdate,
    base_params: PyTree,
    base_hash: str,
    *,
    quantize: bool = False,
    density_threshold: float = 0.5,
    compress: str = "none",
) -> bytes:
    """Encode ``update`` as a sparse diff against ``base_params`` (whose full
    serialized blob hashes to ``base_hash``). Leaves denser than
    ``density_threshold`` fall back to dense storage; ``quantize`` ships the
    changed values int8-quantized (lossy — drop it when bitwise equality with
    the full-blob path matters)."""
    new, base = _flat_wire(update.params), _flat_wire(base_params)
    if set(new) != set(base):
        raise ValueError("delta requires identical tree structure with the base")
    arrays: dict[str, np.ndarray] = {}
    order: list[str] = []
    dtypes: dict[str, str] = {}
    for key, (a, dt) in new.items():
        order.append(key)
        if dt:
            dtypes[key] = dt
        b = base[key][0]
        if a.shape != b.shape or a.dtype != b.dtype:
            arrays[_DENSE + key] = a
            continue
        af, bf = a.reshape(-1), b.reshape(-1)
        idx = np.flatnonzero(af != bf)
        if idx.size > density_threshold * af.size:
            arrays[_DENSE + key] = a
            continue
        arrays[_IDX + key] = idx.astype(np.int64 if af.size > 2**31 else np.int32)
        vals = af[idx]
        if quantize and vals.dtype.kind == "f" and vals.size:
            q, scale = quantize_leaf(vals)
            arrays[_VAL + key] = q
            arrays[_SCALE + key] = np.asarray(scale)
        else:
            arrays[_VAL + key] = vals
    return _pack_npz(arrays, order, dtypes, _update_meta(update, delta_of=base_hash),
                     compress=compress)


def deserialize_update_delta(blob: bytes, base_params: PyTree) -> NodeUpdate:
    """Reconstruct a full NodeUpdate from a delta blob + the base params it
    was diffed against (the caller is responsible for matching ``delta_of`` to
    the base blob's content hash; see WeightStore)."""
    base = _flat_wire(base_params)
    env = NpzEnvelope(blob)
    meta, order, dtypes = env.contents()
    if "delta_of" not in meta:
        raise ValueError("not a delta blob")
    data = env.read(env.files)

    def reconstruct(key: str) -> np.ndarray:
        if _DENSE + key in data:
            arr = data[_DENSE + key]
            if _SCALE + key in data:  # dense int8-quantized leaf
                arr = dequantize_leaf(arr, data[_SCALE + key])
            return arr
        if key not in base:
            raise DeltaBaseMismatch(f"base is missing leaf {key!r}")
        b = base[key][0]
        flat = np.array(b, copy=True).reshape(-1)
        idx = data[_IDX + key]
        vals = data[_VAL + key]
        if _SCALE + key in data:
            vals = dequantize_leaf(vals, data[_SCALE + key])
        flat[idx] = vals.astype(flat.dtype, copy=False)
        return flat.reshape(b.shape)

    return _update_from_meta(_rebuild_tree(order, dtypes, reconstruct), meta)
