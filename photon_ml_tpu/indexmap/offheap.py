"""Off-heap partitioned feature index map: the PalDB-equivalent native store.

Reference parity: util/PalDBIndexMap.scala:43 (partitioned read-only mmap
stores, name->index and index->name in one store :69-103),
PalDBIndexMapBuilder.scala:27 (per-partition store build) and
FeatureIndexingJob.scala:56 (hash-partitioned distinct features -> one store
per partition). The store format ("PHIX") and its C++ reader/builder live in
photon_ml_tpu/native/indexstore.cpp; this module compiles that file on demand
(g++ -O2 -shared), binds it via ctypes, and falls back to a pure-Python mmap
reader/writer of the SAME format when no compiler is available — files are
interchangeable between both implementations.

Partitioning: key -> partition by fnv1a64(key) % num_partitions (stable
across Python/C++). Global indices are assigned contiguously per partition;
``partition_offsets`` in metadata.json lets reverse lookup binary-search the
owning partition.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import mmap
import os
import pathlib
import struct
import subprocess
import threading
from typing import Iterable, List, Optional, Sequence

import numpy as np

from photon_ml_tpu.indexmap import IndexMap

_NATIVE_DIR = pathlib.Path(__file__).resolve().parent.parent / "native"
_SRC = _NATIVE_DIR / "indexstore.cpp"
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False

_FNV_OFFSET = np.uint64(14695981039346656037)
_FNV_PRIME = np.uint64(1099511628211)

METADATA_FILE = "metadata.json"
PARTITION_FILE = "partition-{i}.bin"

_HEADER = struct.Struct("<4sIQQQQQQ")  # magic, version, slots, entries, fwd, rev, keys_off, keys_len
_MAGIC = b"PHIX"
_EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)


def _load_native() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the native store; None on a host without
    g++ (a compiler that fails on the committed source raises)."""
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        from photon_ml_tpu.utils.nativelib import build_and_load

        lib = build_and_load(_SRC)
        if lib is None:
            _lib_failed = True
            return None
        lib.phix_build.restype = ctypes.c_int
        lib.phix_build.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.phix_open.restype = ctypes.c_void_p
        lib.phix_open.argtypes = [ctypes.c_char_p]
        lib.phix_get.restype = ctypes.c_int64
        lib.phix_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        lib.phix_get_batch.restype = None
        lib.phix_get_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64,
        ]
        lib.phix_name_at.restype = ctypes.c_int64
        lib.phix_name_at.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
        ]
        lib.phix_num_entries.restype = ctypes.c_uint64
        lib.phix_num_entries.argtypes = [ctypes.c_void_p]
        lib.phix_close.restype = None
        lib.phix_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load_native() is not None


def _pack_keys(names: Sequence[bytes]):
    """Concatenate byte keys -> (blob, offsets u64, lens u32)."""
    lens = np.fromiter((len(n) for n in names), dtype=np.uint32, count=len(names))
    offs = np.zeros(len(names), dtype=np.uint64)
    if len(names) > 1:
        offs[1:] = np.cumsum(lens[:-1], dtype=np.uint64)
    return b"".join(names), offs, lens


def fnv1a_hashes(names: Sequence[bytes]) -> np.ndarray:
    """Vectorized FNV-1a 64 over byte keys (partition routing; identical to
    the C++ fnv1a in indexstore.cpp)."""
    if not len(names):
        return np.zeros(0, dtype=np.uint64)
    lens = np.fromiter((len(n) for n in names), dtype=np.int64, count=len(names))
    max_len = int(lens.max()) if len(lens) else 0
    buf = np.zeros((len(names), max_len), dtype=np.uint8)
    for i, n in enumerate(names):
        buf[i, : len(n)] = np.frombuffer(n, dtype=np.uint8)
    h = np.full(len(names), _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for j in range(max_len):
            live = j < lens
            h[live] = (h[live] ^ buf[live, j].astype(np.uint64)) * _FNV_PRIME
    return h


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _pow2_slots(n: int) -> int:
    want = (n * 10) // 7 + 1
    s = 16
    while s < want:
        s <<= 1
    return s


def _build_partition_python(
    path: str, names: Sequence[bytes], indices: np.ndarray
) -> None:
    """Pure-Python writer of the PHIX format (fallback; file-identical
    semantics to phix_build)."""
    n = len(names)
    slots = _pow2_slots(n)
    mask = np.uint64(slots - 1)
    blob, offs, lens = _pack_keys(names)

    fwd_off = np.full(slots, _EMPTY, dtype=np.uint64)
    fwd_len = np.zeros(slots, dtype=np.uint32)
    fwd_idx = np.zeros(slots, dtype=np.uint32)
    rev_ip1 = np.zeros(slots, dtype=np.uint64)
    rev_off = np.zeros(slots, dtype=np.uint64)
    rev_len = np.zeros(slots, dtype=np.uint32)

    hashes = fnv1a_hashes(names)
    rhashes = _splitmix64(np.asarray(indices, dtype=np.uint64))
    for i in range(n):
        slot = int(hashes[i] & mask)
        while fwd_off[slot] != _EMPTY:
            if fwd_len[slot] == lens[i] and blob[
                int(fwd_off[slot]) : int(fwd_off[slot]) + int(lens[i])
            ] == names[i]:
                raise ValueError(f"duplicate key {names[i]!r}")
            slot = (slot + 1) % slots
        fwd_off[slot] = offs[i]
        fwd_len[slot] = lens[i]
        fwd_idx[slot] = indices[i]
        rslot = int(rhashes[i] & mask)
        while rev_ip1[rslot] != 0:
            rslot = (rslot + 1) % slots
        rev_ip1[rslot] = np.uint64(int(indices[i]) + 1)
        rev_off[rslot] = offs[i]
        rev_len[rslot] = lens[i]

    fwd = np.zeros(slots, dtype=[("off", "<u8"), ("len", "<u4"), ("idx", "<u4")])
    fwd["off"], fwd["len"], fwd["idx"] = fwd_off, fwd_len, fwd_idx
    rev = np.zeros(
        slots, dtype=[("ip1", "<u8"), ("off", "<u8"), ("len", "<u4"), ("pad", "<u4")]
    )
    rev["ip1"], rev["off"], rev["len"] = rev_ip1, rev_off, rev_len

    header_size = _HEADER.size
    fwd_bytes = fwd.tobytes()
    rev_bytes = rev.tobytes()
    header = _HEADER.pack(
        _MAGIC, 1, slots, n,
        header_size,
        header_size + len(fwd_bytes),
        header_size + len(fwd_bytes) + len(rev_bytes),
        len(blob),
    )
    with open(path, "wb") as f:
        f.write(header)
        f.write(fwd_bytes)
        f.write(rev_bytes)
        f.write(blob)


def _build_partition(path: str, names: Sequence[bytes], indices: np.ndarray) -> None:
    lib = _load_native()
    if lib is None:
        _build_partition_python(path, names, indices)
        return
    blob, offs, lens = _pack_keys(names)
    idx = np.ascontiguousarray(indices, dtype=np.uint32)
    rc = lib.phix_build(
        str(path).encode(), blob,
        offs.ctypes.data_as(ctypes.c_void_p),
        np.ascontiguousarray(lens).ctypes.data_as(ctypes.c_void_p),
        idx.ctypes.data_as(ctypes.c_void_p),
        len(names),
    )
    if rc != 0:
        raise OSError(f"phix_build failed with code {rc} for {path}")


class _PythonPartition:
    """mmap reader of one PHIX partition (fallback)."""

    def __init__(self, path: str):
        self._f = open(path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        magic, version, slots, entries, fwd_off, rev_off, keys_off, keys_len = (
            _HEADER.unpack_from(self._mm, 0)
        )
        if magic != _MAGIC or version != 1:
            raise ValueError(f"not a PHIX v1 store: {path}")
        self.num_entries = entries
        self._slots = slots
        self._buf = memoryview(self._mm)
        self._fwd = np.frombuffer(
            self._buf, dtype=[("off", "<u8"), ("len", "<u4"), ("idx", "<u4")],
            count=slots, offset=fwd_off,
        )
        self._rev = np.frombuffer(
            self._buf,
            dtype=[("ip1", "<u8"), ("off", "<u8"), ("len", "<u4"), ("pad", "<u4")],
            count=slots, offset=rev_off,
        )
        self._keys_off = keys_off

    def get(self, key: bytes, h: int) -> int:
        mask = self._slots - 1
        slot = int(h) & mask
        mm, ko = self._mm, self._keys_off
        while self._fwd["off"][slot] != _EMPTY:
            off = int(self._fwd["off"][slot])
            ln = int(self._fwd["len"][slot])
            if ln == len(key) and mm[ko + off : ko + off + ln] == key:
                return int(self._fwd["idx"][slot])
            slot = (slot + 1) & mask
        return -1

    def name_at(self, index: int) -> Optional[bytes]:
        mask = self._slots - 1
        slot = int(_splitmix64(np.asarray([index], dtype=np.uint64))[0]) & mask
        want = index + 1
        while self._rev["ip1"][slot] != 0:
            if int(self._rev["ip1"][slot]) == want:
                off = self._keys_off + int(self._rev["off"][slot])
                return self._mm[off : off + int(self._rev["len"][slot])]
            slot = (slot + 1) & mask
        return None

    def close(self) -> None:
        # numpy views over the mmap must be dropped before closing it
        self._fwd = None
        self._rev = None
        self._buf.release()
        self._mm.close()
        self._f.close()


class _NativePartition:
    def __init__(self, path: str, lib: ctypes.CDLL):
        self._lib = lib
        self._h = lib.phix_open(str(path).encode())
        if not self._h:
            raise OSError(f"phix_open failed for {path}")
        self.num_entries = int(lib.phix_num_entries(self._h))

    def get(self, key: bytes, h: int) -> int:
        return int(self._lib.phix_get(self._h, key, len(key)))

    def get_batch(self, blob: bytes, offs: np.ndarray, lens: np.ndarray) -> np.ndarray:
        out = np.empty(len(lens), dtype=np.int64)
        self._lib.phix_get_batch(
            self._h, blob,
            np.ascontiguousarray(offs, dtype=np.uint64).ctypes.data_as(ctypes.c_void_p),
            np.ascontiguousarray(lens, dtype=np.uint32).ctypes.data_as(ctypes.c_void_p),
            out.ctypes.data_as(ctypes.c_void_p),
            len(lens),
        )
        return out

    def name_at(self, index: int) -> Optional[bytes]:
        buf = ctypes.create_string_buffer(4096)
        n = self._lib.phix_name_at(self._h, index, buf, 4096)
        if n < 0:
            return None
        if n > 4096:  # rare: longer than the buffer, retry exact
            buf = ctypes.create_string_buffer(n)
            self._lib.phix_name_at(self._h, index, buf, n)
        return buf.raw[: min(n, len(buf.raw))]

    def close(self) -> None:
        if self._h:
            self._lib.phix_close(self._h)
            self._h = None


def build_offheap_index_map(
    names: Iterable[str],
    output_dir: str,
    num_partitions: int = 1,
) -> "OffHeapIndexMap":
    """Distinct, hash-partition, and store feature names; assign contiguous
    global indices per partition (reference FeatureIndexingJob.scala:92-179).
    Returns the opened map."""
    out = pathlib.Path(output_dir)
    out.mkdir(parents=True, exist_ok=True)
    uniq = sorted(set(names))
    keys = [n.encode("utf-8") for n in uniq]
    part_of = (
        (fnv1a_hashes(keys) % np.uint64(num_partitions)).astype(np.int64)
        if keys
        else np.zeros(0, dtype=np.int64)
    )

    offsets: List[int] = []
    next_index = 0
    for p in range(num_partitions):
        members = [i for i in range(len(keys)) if part_of[i] == p]
        offsets.append(next_index)
        indices = np.arange(next_index, next_index + len(members), dtype=np.uint32)
        _build_partition(
            str(out / PARTITION_FILE.format(i=p)),
            [keys[i] for i in members],
            indices,
        )
        next_index += len(members)

    (out / METADATA_FILE).write_text(
        json.dumps(
            {
                "format": "PHIX",
                "version": 1,
                "num_partitions": num_partitions,
                "num_entries": len(uniq),
                "partition_offsets": offsets,
            }
        )
    )
    return OffHeapIndexMap(output_dir)


class OffHeapIndexMap(IndexMap):
    """Partitioned mmap'd feature index map (reference PalDBIndexMap.scala:43).

    Opens every partition store (native if possible, pure-Python otherwise).
    Forward lookup routes by fnv1a(key) % P; reverse lookup binary-searches
    ``partition_offsets`` (indices are contiguous per partition).
    """

    def __init__(self, directory: str):
        meta = json.loads((pathlib.Path(directory) / METADATA_FILE).read_text())
        if meta.get("format") != "PHIX":
            raise ValueError(f"{directory} is not a PHIX index map directory")
        self._dir = str(directory)
        self._num_partitions = int(meta["num_partitions"])
        self._num_entries = int(meta["num_entries"])
        self._offsets = np.asarray(meta["partition_offsets"], dtype=np.int64)
        lib = _load_native()
        self._parts = []
        for p in range(self._num_partitions):
            path = str(pathlib.Path(directory) / PARTITION_FILE.format(i=p))
            self._parts.append(
                _NativePartition(path, lib) if lib else _PythonPartition(path)
            )

    def get_index(self, name: str) -> int:
        key = name.encode("utf-8")
        h = int(fnv1a_hashes([key])[0])
        return self._parts[h % self._num_partitions].get(key, h)

    def get_indices(self, names: Sequence[str]) -> np.ndarray:
        keys = [n.encode("utf-8") for n in names]
        if not keys:
            return np.zeros(0, dtype=np.int64)
        hashes = fnv1a_hashes(keys)
        parts = (hashes % np.uint64(self._num_partitions)).astype(np.int64)
        out = np.empty(len(keys), dtype=np.int64)
        for p in range(self._num_partitions):
            sel = np.nonzero(parts == p)[0]
            if not len(sel):
                continue
            part = self._parts[p]
            if isinstance(part, _NativePartition):
                blob, offs, lens = _pack_keys([keys[i] for i in sel])
                out[sel] = part.get_batch(blob, offs, lens)
            else:
                for i in sel:
                    out[i] = part.get(keys[i], int(hashes[i]))
        return out

    def get_feature_name(self, index: int) -> Optional[str]:
        if index < 0 or index >= self._num_entries:
            return None
        p = int(np.searchsorted(self._offsets, index, side="right")) - 1
        raw = self._parts[p].name_at(int(index))
        return raw.decode("utf-8") if raw is not None else None

    def __len__(self) -> int:
        return self._num_entries

    def content_digest(self) -> str:
        """Digest of the store directory's file identities — (name, size,
        mtime_ns) of metadata + every partition — instead of the base
        class's O(entries) reverse scan. PHIX stores are immutable once
        built, so file identity IS content identity; a rebuilt store (even
        with identical entries) digests differently, which can only cause
        a spurious cache miss, never a stale hit."""
        h = hashlib.sha256()
        for name in sorted(os.listdir(self._dir)):
            st = os.stat(os.path.join(self._dir, name))
            h.update(
                f"{name}\x00{st.st_size}\x00{st.st_mtime_ns}\x01".encode("utf-8")
            )
        return h.hexdigest()

    def close(self) -> None:
        for p in self._parts:
            p.close()
        self._parts = []

    def __enter__(self) -> "OffHeapIndexMap":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
