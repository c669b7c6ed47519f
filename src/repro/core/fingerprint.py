"""On-device chunk fingerprints — the TPU-native change detector (C1).

The paper diffs text on the host. At TPU scale the params live in HBM and
hauling bytes to the host to hash them costs O(bytes/PCIe-bw) per save. We
instead compute a 64-bit mixing fingerprint per chunk *on device* — reading
each byte once at HBM bandwidth — and ship only the (n_chunks, 2) int32
fingerprint table to the host. Chunks whose fingerprint changed since the
last save are then fetched and SHA-256'd for the store (the key+lock hash
stays SHA-256, faithful to the paper; the fingerprint is a pre-filter).

Both reductions (xor, wraparound-add) are associative + commutative, so the
result is bit-identical under any sharding/layout — required for a
distributed change detector.

Two granularities:

* ``fingerprint_chunks`` — one tensor per call. Fine for a handful of big
  arrays, but a real checkpoint has hundreds of pytree leaves and one jitted
  dispatch + one D2H transfer *per leaf* is dispatch-bound.
* ``fingerprint_tree_packed`` — the whole checkpoint in ONE dispatch: every
  leaf's uint32 lanes are packed into a single padded ``(total_chunks,
  lanes)`` buffer with a host-side index table mapping buffer rows back to
  ``(tensor, chunk_idx)``. Rows narrower than the widest leaf are masked
  past their own width, so each row's fingerprint is bit-identical to the
  per-leaf path. A single ``(total_chunks, 2)`` table (8 B per chunk)
  crosses the host link.

The Pallas kernel in kernels/fingerprint/ implements the same mix with
explicit VMEM tiling; this module is the jnp path (and the kernel's oracle).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .chunker import dtype_itemsize

# odd multipliers from splitmix64's constants (truncated to 32-bit, forced odd)
_C1 = np.uint32(0x9E3779B9)
_C2 = np.uint32(0x85EBCA6B)
_C3 = np.uint32(0xC2B2AE35)


def _to_u32_lanes(arr: jax.Array) -> jax.Array:
    """Bit-exact view of any array as a flat uint32 lane vector."""
    a = arr.reshape(-1)
    nbits = jnp.dtype(a.dtype).itemsize * 8
    if a.dtype == jnp.bool_:
        a = a.astype(jnp.uint8)
        nbits = 8
    if nbits == 64:
        a = jax.lax.bitcast_convert_type(a, jnp.uint32).reshape(-1)
        return a
    if nbits == 32:
        return jax.lax.bitcast_convert_type(a, jnp.uint32)
    # sub-32-bit: widen bit patterns (cheap, keeps all entropy)
    if nbits == 16:
        u = jax.lax.bitcast_convert_type(a, jnp.uint16)
    else:  # 8-bit
        u = jax.lax.bitcast_convert_type(a, jnp.uint8)
    return u.astype(jnp.uint32)


def chunk_geometry(shape: Tuple[int, ...], dtype: str,
                   chunk_bytes: int) -> Tuple[int, int]:
    """-> (n_chunks, lanes_per_chunk) for a tensor, matching both
    chunker.iter_chunks boundaries on the serialized bytes and the lane
    layout produced by ``_to_u32_lanes`` (sub-32-bit dtypes widen to one
    lane per element; 64-bit dtypes split into two lanes per element)."""
    itemsize = dtype_itemsize(dtype)
    lanes_per_elem = 2 if itemsize == 8 else 1
    elems_per_chunk = max(1, chunk_bytes // itemsize)
    n = 1
    for s in shape:
        n *= int(s)
    if not shape:
        n = 1
    n_chunks = max(1, -(-n // elems_per_chunk))
    lanes_per_chunk = elems_per_chunk * lanes_per_elem if n else 1
    return n_chunks, lanes_per_chunk


def _mix(u: jax.Array, pos: jax.Array) -> jax.Array:
    """The multiply-xor-shift lane mix (identical in jnp/numpy/Pallas)."""
    mixed = (u * _C1) ^ (pos * _C2 + _C3)
    mixed = mixed ^ (mixed >> 15)
    return mixed * _C3


def _reduce_rows(mixed: jax.Array) -> jax.Array:
    fp_xor = jax.lax.reduce(mixed, np.uint32(0),
                            jax.lax.bitwise_xor, dimensions=(1,))
    fp_sum = jnp.sum(mixed, axis=1, dtype=jnp.uint32)
    out = jnp.stack([fp_xor, fp_sum], axis=-1)
    return jax.lax.bitcast_convert_type(out, jnp.int32)


@functools.partial(jax.jit, static_argnames=("chunk_bytes",))
def fingerprint_chunks(arr: jax.Array, chunk_bytes: int = 1 << 20) -> jax.Array:
    """-> (n_chunks, 2) int32 fingerprints, chunk boundaries matching
    chunker.iter_chunks on the serialized bytes."""
    n_chunks, lanes_per_chunk = chunk_geometry(
        tuple(arr.shape), str(arr.dtype), chunk_bytes)
    u = _to_u32_lanes(arr)
    pad = n_chunks * lanes_per_chunk - u.size
    u = jnp.pad(u, (0, pad))
    u = u.reshape(n_chunks, lanes_per_chunk)
    pos = jnp.arange(lanes_per_chunk, dtype=jnp.uint32)[None, :]
    return _reduce_rows(_mix(u, pos))


def _device_lanes_leaf(v):
    """jnp.asarray that survives disabled x64: 64-bit numpy leaves
    (arrays AND scalars — np.generic) are bit-viewed as uint32 lanes on
    the host (jnp.asarray would silently downcast them, making the
    fingerprint blind to low-order bits of the serialized value). The
    uint32 view is the exact lane stream ``_to_u32_lanes`` produces."""
    if isinstance(v, np.generic):
        v = np.asarray(v)
    if isinstance(v, np.ndarray) and v.dtype.itemsize == 8 and \
            v.dtype != np.bool_ and not getattr(jax.config, "jax_enable_x64",
                                                False):
        return jnp.asarray(np.ascontiguousarray(v).reshape(-1).view(np.uint32))
    return jnp.asarray(v)


def fingerprint_tree(tree, chunk_bytes: int = 1 << 20) -> Dict[str, np.ndarray]:
    """Host-side convenience: name->fingerprints for a flat payload dict.

    One device dispatch and one D2H transfer PER LEAF — kept as the
    dispatch-bound baseline that ``fingerprint_tree_packed`` is benchmarked
    against (benchmarks/run.py::bench_incremental_save).
    """
    out: Dict[str, np.ndarray] = {}
    for name, v in tree.items():
        n_chunks, lanes = chunk_geometry(tuple(np.shape(v)), str(v.dtype),
                                         chunk_bytes)
        fp = _fingerprint_packed((_device_lanes_leaf(v),),
                                 ((n_chunks, lanes),), lanes, "jnp", False)
        out[name] = np.asarray(fp)
    return out


# --------------------------------------------------------------------- packed
def tree_pack_index(tree, chunk_bytes: int
                    ) -> Tuple[List[Tuple[str, int, int]], int, int]:
    """Host-side index table for the packed buffer.

    -> ([(name, row_offset, n_chunks), ...], total_chunks, max_lanes).
    Row ``row_offset + j`` of the packed buffer holds chunk ``j`` of
    ``name`` — the map from packed rows back to (tensor, chunk_idx).
    """
    index: List[Tuple[str, int, int]] = []
    row = 0
    max_lanes = 1
    for name, v in tree.items():
        n_chunks, lanes = chunk_geometry(
            tuple(np.shape(v)), str(v.dtype), chunk_bytes)
        index.append((name, row, n_chunks))
        row += n_chunks
        max_lanes = max(max_lanes, lanes)
    return index, row, max_lanes


def _pack_rows(leaves: Tuple[jax.Array, ...],
               geom: Tuple[Tuple[int, int], ...],
               lanes: int, row_multiple: int = 1
               ) -> Tuple[jax.Array, jax.Array]:
    """Trace-time packing: (total_chunks, lanes) uint32 buffer + per-row
    width vector. Rows keep each leaf's OWN zero padding inside its width
    (bit-identical to the per-leaf path); columns past the width are
    masked out by the consumer. Zero-width rows pad the row count up to
    ``row_multiple`` inside the same concatenation, so a consumer that
    blocks rows (the Pallas kernel) needs no second padded copy."""
    rows = []
    for arr, (n_chunks, w) in zip(leaves, geom):
        u = _to_u32_lanes(arr)
        u = jnp.pad(u, (0, n_chunks * w - u.size)).reshape(n_chunks, w)
        if w < lanes:
            u = jnp.pad(u, ((0, 0), (0, lanes - w)))
        rows.append(u)
    widths = [np.full(g[0], g[1], np.int32) for g in geom]
    pad = -sum(g[0] for g in geom) % row_multiple
    if pad:
        rows.append(jnp.zeros((pad, lanes), jnp.uint32))
        widths.append(np.zeros(pad, np.int32))
    u_all = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
    widths = np.concatenate(widths) if widths else np.zeros((0,), np.int32)
    return u_all, jnp.asarray(widths)


@functools.partial(jax.jit,
                   static_argnames=("geom", "lanes", "backend", "interpret"))
def _fingerprint_packed(leaves: Tuple[jax.Array, ...],
                        geom: Tuple[Tuple[int, int], ...],
                        lanes: int, backend: str, interpret: bool
                        ) -> jax.Array:
    if backend == "pallas":
        from ..kernels.fingerprint.kernel import ROWS, fingerprint_lanes
        u_all, widths = _pack_rows(leaves, geom, lanes, row_multiple=ROWS)
        fp = fingerprint_lanes(u_all, widths=widths, interpret=interpret)
        return fp[:sum(g[0] for g in geom)]
    u_all, widths = _pack_rows(leaves, geom, lanes)
    pos = jnp.arange(lanes, dtype=jnp.uint32)[None, :]
    mixed = _mix(u_all, pos)
    mixed = jnp.where(pos < widths.astype(jnp.uint32)[:, None],
                      mixed, jnp.uint32(0))
    return _reduce_rows(mixed)


def fingerprint_tree_packed(tree, chunk_bytes: int = 1 << 20, *,
                            backend: str = "jnp", interpret: bool = False,
                            stats: Optional[dict] = None
                            ) -> Dict[str, np.ndarray]:
    """Fingerprint an entire flat payload dict in ONE device dispatch.

    Drop-in replacement for ``fingerprint_tree``: returns the identical
    name -> (n_chunks, 2) int32 table (bit-for-bit), but issues a single
    fused jitted computation over a packed ``(total_chunks, max_lanes)``
    buffer and a single D2H transfer of the ``(total_chunks, 2)`` result,
    instead of one dispatch + one transfer per pytree leaf.

    ``backend``: "jnp" (XLA, also the CPU path) or "pallas" (the tiled TPU
    kernel in kernels/fingerprint/; ``interpret=True`` runs it on CPU).
    ``stats``: optional dict; accumulates "bytes_d2h" (fingerprint-table
    bytes shipped to host) and "device_dispatches".

    Memory note: leaves are padded to the widest leaf's lane count —
    mixed-itemsize trees pay up to 4x transient padding on the narrow
    leaves. Homogeneous checkpoints (the common case) pay only the final
    ragged chunk per leaf.
    """
    if not tree:
        return {}
    names = list(tree.keys())
    index, total_chunks, max_lanes = tree_pack_index(tree, chunk_bytes)
    leaves = tuple(_device_lanes_leaf(tree[name]) for name in names)
    geom = tuple(chunk_geometry(tuple(np.shape(tree[n])), str(tree[n].dtype),
                                chunk_bytes) for n in names)
    fp_all = np.asarray(_fingerprint_packed(leaves, geom, max_lanes,
                                            backend, interpret))
    if stats is not None:
        stats["bytes_d2h"] = stats.get("bytes_d2h", 0) + fp_all.nbytes
        stats["device_dispatches"] = stats.get("device_dispatches", 0) + 1
    return {name: fp_all[off:off + n] for name, off, n in index}


def fingerprint_chunks_ref(arr: np.ndarray, chunk_bytes: int = 1 << 20) -> np.ndarray:
    """Pure-numpy oracle (also the ref for the Pallas kernel)."""
    a = np.asarray(arr)
    if str(a.dtype) == "bfloat16":
        u = a.view(np.uint16).astype(np.uint32).reshape(-1)
        itemsize = 2
    elif a.dtype == np.bool_:
        u = a.astype(np.uint8).astype(np.uint32).reshape(-1)
        itemsize = 1
    elif a.dtype.itemsize == 8:
        u = a.reshape(-1).view(np.uint32)
        itemsize = 8
    elif a.dtype.itemsize == 4:
        u = a.reshape(-1).view(np.uint32)
        itemsize = 4
    elif a.dtype.itemsize == 2:
        u = a.reshape(-1).view(np.uint16).astype(np.uint32)
        itemsize = 2
    else:
        u = a.reshape(-1).view(np.uint8).astype(np.uint32)
        itemsize = 1
    n = a.size
    elems_per_chunk = max(1, chunk_bytes // itemsize)
    n_chunks = max(1, -(-n // elems_per_chunk))
    lanes_per_chunk = (elems_per_chunk * u.size) // max(n, 1) if n else 1
    pad = n_chunks * lanes_per_chunk - u.size
    u = np.pad(u, (0, pad)).reshape(n_chunks, lanes_per_chunk)
    pos = np.arange(lanes_per_chunk, dtype=np.uint32)[None, :]
    with np.errstate(over="ignore"):
        mixed = (u * _C1) ^ (pos * _C2 + _C3)
        mixed = mixed ^ (mixed >> np.uint32(15))
        mixed = mixed * _C3
        fp_xor = np.bitwise_xor.reduce(mixed, axis=1)
        fp_sum = np.add.reduce(mixed, axis=1, dtype=np.uint32)
    return np.stack([fp_xor, fp_sum], axis=-1).view(np.int32)


def fingerprint_tree_ref(tree, chunk_bytes: int = 1 << 20
                         ) -> Dict[str, np.ndarray]:
    """Numpy oracle for a whole flat payload dict (no device round-trip)."""
    return {name: fingerprint_chunks_ref(np.asarray(v), chunk_bytes)
            for name, v in tree.items()}


@functools.lru_cache(maxsize=16)
def _pos_term(lanes: int) -> np.ndarray:
    """``pos * C2 + C3`` over one chunk's lane positions (read-only; one
    per lane width, so a save computes it once per dtype width)."""
    term = np.arange(lanes, dtype=np.uint32) * _C2 + _C3
    term.flags.writeable = False
    return term


def fingerprint_chunk_bytes(data, dtype: str, chunk_bytes: int = 1 << 20
                            ) -> Optional[Tuple[int, int]]:
    """Fingerprint ONE serialized chunk — bit-identical to the row this
    chunk gets in ``fingerprint_chunks_ref`` over the whole tensor (lane
    positions restart at 0 per chunk; a partial final chunk zero-pads to
    the full lane width). Host-side, used to refresh the ``TensorRecord.fp``
    sidecar for injected chunks (only changed chunks ever pay this).

    ``data`` is any bytes-like object and is only read: 64-bit elements
    are two uint32 lanes, sub-32-bit elements widen to one lane each. The
    mix runs in place in one lane buffer, with the position term cached.

    Returns None for pathological chunk sizes that do not align to the
    dtype's itemsize: a mid-tensor chunk then splits elements across chunk
    boundaries and no per-chunk recompute can match the whole-tensor
    table — callers drop the sidecar instead of crashing.
    """
    itemsize = dtype_itemsize(dtype)
    if chunk_bytes % itemsize or len(data) % itemsize:
        return None
    lane = np.dtype(f"u{min(itemsize, 4)}")
    u = np.frombuffer(data, dtype=lane)
    # an empty tensor is one chunk of one zero lane, as in the oracle
    width = chunk_bytes // lane.itemsize if u.size else 1
    mixed = np.empty(width, np.uint32)
    np.multiply(u, _C1, out=mixed[:u.size], dtype=np.uint32)
    mixed[u.size:] = 0
    mixed ^= _pos_term(width)
    mixed ^= mixed >> np.uint32(15)
    mixed *= _C3
    fp_xor = np.bitwise_xor.reduce(mixed)
    fp_sum = np.add.reduce(mixed, dtype=np.uint32)
    out = np.array([fp_xor, fp_sum], np.uint32).view(np.int32)
    return int(out[0]), int(out[1])
