"""Tensor <-> content-addressed chunk serialization.

A pytree leaf is serialized to raw little-endian bytes and split into
fixed-size chunks. Chunks are the smallest addressable unit of the store —
the analogue of files inside a Docker ``layer.tar``. The chunk boundary is
what makes the paper's injection O(delta): an edit touching k chunks costs
k chunk writes + k hashes, independent of layer size.

Hot-path mechanics (the save pipeline, see also core/diff.py):

* ``tensor_byte_view`` is a leaf's serialized bytes as a flat, byte-format
  ``memoryview`` of the array itself — no copy for a C-contiguous leaf.
  The host diff slices it into chunks and reads each slice once: SHA-256
  and, for a changed chunk, its fingerprint sidecar in the same task; the
  changed slices go to the blob writes as they are.
* ``iter_chunks`` yields zero-copy ``memoryview`` slices — splitting a
  serialized tensor allocates nothing; bytes are only copied when a chunk
  is written.
* ``map_chunks`` runs one task per chunk on a shared ``ThreadPoolExecutor``
  (``hash_chunks`` is its SHA-256-only form) — CPython's hashlib and
  numpy's ufuncs release the GIL on large buffers, so a multi-chunk tensor
  scales across cores; a small batch runs inline.
* ``tensor_chunk_bytes`` copies ONE chunk's byte range of a tensor.
"""
from __future__ import annotations

import hashlib
import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Sequence, Tuple, TypeVar

import numpy as np

DEFAULT_CHUNK_BYTES = 1 << 20  # 1 MiB

# Shared hashing pool. hashlib releases the GIL on large buffers, so SHA-256
# over many chunks parallelizes well; small batches stay on the caller
# thread to avoid pool dispatch overhead.
_HASH_POOL_WORKERS = min(8, os.cpu_count() or 1)
_HASH_POOL = ThreadPoolExecutor(max_workers=_HASH_POOL_WORKERS,
                                thread_name_prefix="repro-sha")
_PARALLEL_MIN_BYTES = 1 << 18   # don't fan out tiny batches


def sha256_hex(data) -> str:
    return hashlib.sha256(data).hexdigest()


def hash_pool() -> Optional[ThreadPoolExecutor]:
    """The shared SHA/transfer executor (None on single-core boxes).

    Shared by chunk hashing and the registry's pipelined blob transfer —
    tasks submitted here must hash inline (``sha256_hex``), never via
    ``hash_chunks`` or ``map_chunks``, so the pool cannot deadlock on
    itself."""
    return _HASH_POOL if _HASH_POOL_WORKERS > 1 else None


T = TypeVar("T")


def map_chunks(fn: Callable[[int, memoryview], T], pieces: Sequence
               ) -> Tuple[List[T], int]:
    """-> (``[fn(i, piece) for i, piece in enumerate(pieces)]``, workers).

    The tasks fan out to the shared pool when the batch is large enough for
    the GIL release to pay off; ``workers`` is the pool width that ran
    them, 1 when they ran inline on the caller thread. ``fn`` runs on the
    pool, so it hashes inline and never calls ``map_chunks`` or
    ``hash_chunks``."""
    pieces = list(pieces)
    if len(pieces) > 1 and _HASH_POOL_WORKERS > 1 and \
            sum(len(p) for p in pieces) >= _PARALLEL_MIN_BYTES:
        return (list(_HASH_POOL.map(fn, range(len(pieces)), pieces)),
                _HASH_POOL_WORKERS)
    return [fn(i, p) for i, p in enumerate(pieces)], 1


def hash_chunks(pieces: Sequence) -> List[str]:
    """SHA-256 a batch of bytes-like chunks (``map_chunks``' pool rule)."""
    return map_chunks(lambda _, p: sha256_hex(p), pieces)[0]


@dataclass(frozen=True)
class TensorRecord:
    """Descriptor of one serialized tensor inside a layer."""

    name: str                 # pytree path, e.g. "params/blocks/attn/wq"
    shape: Tuple[int, ...]
    dtype: str                # numpy dtype string, e.g. "bfloat16"
    chunk_bytes: int
    chunks: Tuple[str, ...]   # sha256 hex of each chunk, in order
    # Optional per-chunk 64-bit fingerprint sidecar ((xor, sum) int32 pairs,
    # see core/fingerprint.py). NOT part of the layer content checksum —
    # purely a cache accelerator: lets build_image's COPY cache check
    # prefilter instead of re-chunking + re-SHA-ing the whole payload.
    fp: Optional[Tuple[Tuple[int, int], ...]] = None

    @property
    def nbytes(self) -> int:
        n = int(np.prod(self.shape)) if self.shape else 1
        return n * dtype_itemsize(self.dtype)

    def to_json(self) -> dict:
        d = {
            "name": self.name,
            "shape": list(self.shape),
            "dtype": self.dtype,
            "chunk_bytes": self.chunk_bytes,
            "chunks": list(self.chunks),
        }
        if self.fp is not None:
            d["fp"] = [list(p) for p in self.fp]
        return d

    @staticmethod
    def from_json(d: dict) -> "TensorRecord":
        fp = d.get("fp")
        return TensorRecord(
            name=d["name"],
            shape=tuple(d["shape"]),
            dtype=d["dtype"],
            chunk_bytes=int(d["chunk_bytes"]),
            chunks=tuple(d["chunks"]),
            fp=tuple(tuple(int(x) for x in p) for p in fp)
            if fp is not None else None,
        )


_DTYPE_SIZES = {
    "bfloat16": 2, "float16": 2, "float32": 4, "float64": 8,
    "int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
    "int32": 4, "uint32": 4, "int64": 8, "uint64": 8, "bool": 1,
}


def dtype_itemsize(dtype: str) -> int:
    if dtype in _DTYPE_SIZES:
        return _DTYPE_SIZES[dtype]
    return np.dtype(dtype).itemsize


def tensor_byte_view(arr) -> memoryview:
    """A leaf's serialized bytes as a flat, byte-format ``memoryview`` of
    the array (numpy or jax; bfloat16, bool and every other dtype by their
    raw bits). No copy for a C-contiguous array; the view keeps the array
    alive."""
    a = np.ascontiguousarray(np.asarray(arr)).reshape(-1)
    return memoryview(a.view(np.uint8))


def tensor_to_bytes(arr) -> bytes:
    """Serialize an array (numpy or jax) to contiguous little-endian bytes."""
    return bytes(tensor_byte_view(arr))


def bytes_to_tensor(data: bytes, shape: Tuple[int, ...], dtype: str) -> np.ndarray:
    if dtype == "bfloat16":
        import ml_dtypes  # ships with jax

        a = np.frombuffer(data, dtype=np.uint16).view(ml_dtypes.bfloat16)
    else:
        a = np.frombuffer(data, dtype=np.dtype(dtype))
    return a.reshape(shape)


def iter_chunks(data, chunk_bytes: int = DEFAULT_CHUNK_BYTES
                ) -> Iterator[memoryview]:
    """Split a bytes-like object into chunk-sized ZERO-COPY memoryviews.

    Byte-identical to slicing ``data`` directly (``bytes(piece)`` recovers
    the old behavior); the underlying buffer must outlive the views.
    """
    mv = memoryview(data)
    for off in range(0, max(len(mv), 1), chunk_bytes):
        yield mv[off:off + chunk_bytes]


def tensor_chunk_bytes(arr, chunk_idx: int,
                       chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> bytes:
    """Serialize ONLY chunk ``chunk_idx`` of a tensor — byte-identical to
    ``tensor_to_bytes(arr)[chunk_idx*cb:(chunk_idx+1)*cb]`` but copies just
    that range."""
    view = tensor_byte_view(arr)
    return bytes(view[chunk_idx * chunk_bytes:(chunk_idx + 1) * chunk_bytes])


def chunk_tensor(name: str, arr, chunk_bytes: int = DEFAULT_CHUNK_BYTES):
    """-> (TensorRecord, [(sha256, memoryview), ...]) for every chunk.

    Chunk payloads are zero-copy views of one serialization buffer; hashing
    fans out to the shared pool for multi-chunk tensors.
    """
    dtype = str(arr.dtype)
    data = tensor_to_bytes(arr)
    pieces = list(iter_chunks(data, chunk_bytes))
    hashes = hash_chunks(pieces)
    pairs: List[Tuple[str, memoryview]] = list(zip(hashes, pieces))
    rec = TensorRecord(
        name=name,
        shape=tuple(int(s) for s in np.shape(arr)),
        dtype=dtype,
        chunk_bytes=chunk_bytes,
        chunks=tuple(hashes),
    )
    return rec, pairs


def assemble_tensor(rec: TensorRecord, read_blob) -> np.ndarray:
    """Rebuild a tensor from its chunk records. ``read_blob(hash)->bytes``."""
    data = b"".join(read_blob(h) for h in rec.chunks)
    return bytes_to_tensor(data, rec.shape, rec.dtype)
