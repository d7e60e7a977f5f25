"""Array + container serialization with numpy-compatible headers.

Reference: cpp/include/raft/core/serialize.hpp:36-126 serializes mdspans to an
iostream with a numpy-format dtype header so host tools can read device dumps.
Here we serialize `jax.Array`/`numpy` arrays as standard ``.npy`` payloads inside
a tiny tagged container, so a file written by raft_tpu is readable with plain
numpy — the same interop goal.

Container format (used by every index's serialize/deserialize — the analog of
neighbors/{ivf_flat,ivf_pq,cagra,brute_force}_serialize.cuh):

    magic  b"RAFTTPU\\0"  (8 bytes)
    version uint32 LE
    meta_len uint64 LE, meta = UTF-8 JSON (scalar params, dtype names, order)
    for each array in meta["arrays"]: a standard .npy blob, in order

Version 2 (crash-safe snapshots, ISSUE 7) hardens both ends of the pipe:

* **write** — path saves go through :func:`raft_tpu.core.fsio.atomic_write`
  (tmp + flush + fsync + rename), so a process killed mid-save leaves the
  previous checkpoint intact, never a torn file; the
  ``serialize.save.write`` faultpoint makes the mid-write kill injectable
  in CPU tier-1.
* **read** — the meta block carries each array's byte length and CRC32.
  A truncated or bit-flipped blob fails the load with
  :class:`SnapshotCorruptError` (a ``ValueError`` that
  ``resilience.classify`` maps to FATAL — never retried) NAMING the bad
  array, instead of whatever tokenizer error ``np.load`` happens to leak.

Version-1 files (no lengths/CRCs) still load through the legacy path.
"""

from __future__ import annotations

import io
import json
import os
import struct
import zlib
from typing import Any, Dict, Mapping, Tuple

import numpy as np

_MAGIC = b"RAFTTPU\x00"
_VERSION = 2


class SnapshotCorruptError(ValueError):
    """A container failed its integrity check (truncation, CRC mismatch,
    garbage header). Classified FATAL: the bytes are gone — the recovery
    action is *reload from another snapshot*, not a retry."""


def serialize_array(stream: io.IOBase, arr) -> None:
    """Write one array as a standard .npy blob (numpy-header format parity with
    reference serialize_mdspan, core/serialize.hpp:91)."""
    np.save(stream, np.asarray(arr), allow_pickle=False)


def deserialize_array(stream: io.IOBase) -> np.ndarray:
    return np.load(stream, allow_pickle=False)


class _CrcSink(io.RawIOBase):
    """Write sink that folds CRC32 and counts bytes, storing nothing —
    the measuring pass of :func:`save_arrays` at O(1) extra memory."""

    def __init__(self):
        self.count = 0
        self.crc = 0

    def writable(self) -> bool:
        return True

    def write(self, b) -> int:
        self.crc = zlib.crc32(b, self.crc) & 0xFFFFFFFF
        self.count += len(b)
        return len(b)


def save_arrays(path_or_stream, meta: Mapping[str, Any], arrays: Mapping[str, Any]) -> None:
    """Save a JSON-meta + named-array container (index checkpoint format).

    Path targets are written atomically (fsio.atomic_write); stream targets
    are the caller's durability problem (in-memory round-trips, sockets).

    Lengths + CRCs must land in the meta block, which PRECEDES the payloads
    in the stream — so arrays are serialized twice: a measuring pass into a
    counting sink, then the real write. That costs a second device fetch
    per jax array but never holds more than np.save's own buffering in
    memory; a checkpoint near HBM/host capacity (the incident class this
    format serves) cannot afford a second in-RAM copy of the index."""
    from raft_tpu.core.fsio import atomic_write
    from raft_tpu.resilience import faultpoint

    meta = dict(meta)
    meta["arrays"] = list(arrays.keys())
    meta["array_bytes"] = {}
    meta["array_crc32"] = {}
    for name in meta["arrays"]:
        sink = _CrcSink()
        serialize_array(sink, arrays[name])
        meta["array_bytes"][name] = sink.count
        meta["array_crc32"][name] = sink.crc

    def write_to(stream) -> None:
        blob_meta = json.dumps(meta).encode("utf-8")
        stream.write(_MAGIC)
        stream.write(struct.pack("<I", _VERSION))
        stream.write(struct.pack("<Q", len(blob_meta)))
        stream.write(blob_meta)
        # mid-write injection site: a fatal here proves the atomic contract
        # (target keeps its previous bytes) in CPU tier-1
        faultpoint("serialize.save.write")
        for name in meta["arrays"]:
            serialize_array(stream, arrays[name])

    if isinstance(path_or_stream, (str, bytes, os.PathLike)):
        with atomic_write(path_or_stream) as stream:
            write_to(stream)
    else:
        write_to(path_or_stream)


def _load_v2(stream, meta) -> Dict[str, np.ndarray]:
    """Length- and CRC-checked array reads (v2 containers)."""
    sizes = meta.get("array_bytes", {})
    crcs = meta.get("array_crc32", {})
    arrays: Dict[str, np.ndarray] = {}
    for name in meta["arrays"]:
        want = int(sizes[name])
        blob = stream.read(want)
        if len(blob) < want:
            raise SnapshotCorruptError(
                f"truncated container: array {name!r} has {len(blob)} of "
                f"{want} bytes — partial write, reload from a snapshot")
        got_crc = zlib.crc32(blob) & 0xFFFFFFFF
        if got_crc != int(crcs[name]):
            raise SnapshotCorruptError(
                f"corrupt container: array {name!r} CRC32 {got_crc:#010x} != "
                f"recorded {int(crcs[name]):#010x} — bit corruption, reload "
                f"from a snapshot")
        try:
            arrays[name] = deserialize_array(io.BytesIO(blob))
        except Exception as e:
            raise SnapshotCorruptError(
                f"corrupt container: array {name!r} passed CRC but failed "
                f"npy parse: {e!r}") from e
    return arrays


def load_arrays(path_or_stream) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """Load a container written by :func:`save_arrays` (v1 or v2).

    The ``serialize.load.read`` faultpoint (round 18) sits at the
    host-side dispatch point of every container read — index ``load()``s,
    ``distributed/snapshot.restore_shard``, and the capacity plane's
    snapshot-backed promotion all pass through here, so an oom/hang on
    the device load path is injectable in CPU tier-1 (the
    saves have carried ``serialize.save.write`` since round 9)."""
    from raft_tpu.resilience import faultpoint

    faultpoint("serialize.load.read")
    own = isinstance(path_or_stream, (str, bytes, os.PathLike))
    stream = open(path_or_stream, "rb") if own else path_or_stream
    try:
        magic = stream.read(8)
        if magic != _MAGIC:
            raise ValueError(f"bad magic {magic!r}: not a raft_tpu container")
        head = stream.read(4)
        if len(head) < 4:
            raise SnapshotCorruptError(
                "truncated container: file ends inside the version field")
        (version,) = struct.unpack("<I", head)
        if version > _VERSION:
            raise ValueError(f"unsupported container version {version}")
        try:
            head = stream.read(8)
            if len(head) < 8:
                raise SnapshotCorruptError(
                    "truncated container: file ends inside the meta length")
            (meta_len,) = struct.unpack("<Q", head)
            raw_meta = stream.read(meta_len)
            if len(raw_meta) < meta_len:
                raise SnapshotCorruptError(
                    f"truncated container: meta block has {len(raw_meta)} of "
                    f"{meta_len} bytes")
            meta = json.loads(raw_meta.decode("utf-8"))
            if version >= 2:
                arrays = _load_v2(stream, meta)
            else:
                arrays = {name: deserialize_array(stream)
                          for name in meta["arrays"]}
        except SnapshotCorruptError:
            raise
        except Exception as e:
            # np.load's header parser and the meta json decode leak
            # tokenize/struct/unicode errors on garbage bytes past a valid
            # magic (UnicodeDecodeError/JSONDecodeError are ValueError
            # subclasses — a bare `except ValueError: raise` let them
            # escape unclassified) — surface one stable exception type
            raise SnapshotCorruptError(
                f"corrupt raft_tpu container: {e!r}") from e
        return meta, arrays
    finally:
        if own:
            stream.close()
