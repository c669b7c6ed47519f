"""Chunk fingerprint — Pallas TPU kernel (the paper's C1 on-device).

Computes the 64-bit multiply-xor fingerprint of every checkpoint chunk at
HBM bandwidth. The grid is 2-D: ``(n_chunks / 8, n_tiles)`` — eight chunk
rows at a time (one sublane each) are streamed through VMEM in
``tile_lanes``-wide inner tiles rather than one whole-chunk block, so

* chunks larger than VMEM work (a 1 MiB bf16 chunk widens to 2 MiB of
  uint32 lanes per row), and
* the Mosaic pipeline double-buffers tile fetches while the VPU mixes the
  previous tile.

Inside a tile, lanes are folded pairwise (``^`` and wraparound ``+``) down
to one ``(8, 128)`` partial per reduction; the partials accumulate across
tiles in the resident output blocks (tile axis ``"arbitrary"``, row axis
``"parallel"``), and the last 128 lanes are reduced by XLA outside the
kernel. Both reductions are associative and commutative, so the fold order
does not change the bits.

A per-row ``widths`` vector (scalar-prefetched into SMEM) masks lanes past
each row's true lane count — this is what lets
``core.fingerprint.fingerprint_tree_packed`` pack tensors of different
dtypes (different lanes-per-chunk) into one padded buffer and fingerprint
an entire checkpoint in a single dispatch. The (n_chunks, 2) table (8 B per
chunk) is all that crosses the host link; only changed chunks are then
fetched and SHA-256'd by the store (core/diff).

Matches core.fingerprint bit-for-bit (same constants, same mix).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C1 = 0x9E3779B9
_C2 = 0x85EBCA6B
_C3 = 0xC2B2AE35

ROWS = 8            # chunk rows per block: the sublane count of a vreg
_LANES = 128        # lanes of a vreg: width of the in-kernel partials
_STEP = 1024        # lanes mixed per inner-loop iteration (8 vregs)

# Default inner tile: 64Ki lanes x 8 rows = 2 MiB of VMEM per buffer, 4 MiB
# double-buffered — inside the 16 MiB default scoped VMEM, large enough to
# amortize grid overhead.
DEFAULT_TILE_LANES = 1 << 16


def _fold(x: jax.Array, op) -> jax.Array:
    """(rows, 2^k * 128) -> (rows, 128) by pairwise halving."""
    while x.shape[1] > _LANES:
        h = x.shape[1] // 2
        x = op(x[:, :h], x[:, h:])
    return x


def _fp_kernel(w_ref, u_ref, x_ref, s_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    tile = u_ref.shape[1]
    step = min(tile, _STEP)
    c1, c2, c3 = (jnp.uint32(_C1), jnp.uint32(_C2), jnp.uint32(_C3))
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, step), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (ROWS, step), 0)
    # lanes at tile-local positions >= limit are past the row's width
    # (ragged rows of a packed buffer, column padding, padding rows): zero
    # is the identity of both reductions, so they contribute nothing
    limit = jnp.zeros((ROWS, step), jnp.int32)
    for r in range(ROWS):
        limit = jnp.where(row == r, w_ref[i * ROWS + r] - j * tile, limit)

    def body(k, acc):
        off = pl.multiple_of(k * step, step)
        u = u_ref[:, pl.ds(off, step)]
        local = lane + off
        pos = (local + j * tile).astype(jnp.uint32)
        mixed = (u * c1) ^ (pos * c2 + c3)
        mixed = mixed ^ (mixed >> jnp.uint32(15))
        mixed = mixed * c3
        mixed = jnp.where(local < limit, mixed, jnp.uint32(0))
        return (acc[0] ^ _fold(mixed, jnp.bitwise_xor),
                acc[1] + _fold(mixed, jnp.add))

    zero = jnp.zeros((ROWS, _LANES), jnp.uint32)
    part_x, part_s = jax.lax.fori_loop(0, tile // step, body, (zero, zero))

    @pl.when(j == 0)
    def _init():
        x_ref[...] = part_x
        s_ref[...] = part_s

    @pl.when(j != 0)
    def _accumulate():
        x_ref[...] = x_ref[...] ^ part_x
        s_ref[...] = s_ref[...] + part_s


def _tile_for(lanes: int, tile_lanes: int | None) -> int:
    cap = tile_lanes or DEFAULT_TILE_LANES
    if cap < _LANES or cap & (cap - 1):
        raise ValueError(f"tile_lanes must be a power of two >= {_LANES}, "
                         f"got {cap}")
    tile = _LANES
    while tile < lanes and tile < cap:
        tile *= 2
    return tile


def fingerprint_lanes(u32_lanes: jax.Array, *,
                      widths: jax.Array | None = None,
                      tile_lanes: int | None = None,
                      interpret: bool = False) -> jax.Array:
    """(n_chunks, lanes) uint32 [+ per-row widths] -> (n_chunks, 2) int32.

    ``widths`` (n_chunks,) int32 gives each row's true lane count; lanes at
    positions >= width are masked out of the reduction. Defaults to the full
    buffer width (the single-tensor case, where every row is dense).
    Callers that can produce a buffer whose row count is already a multiple
    of ``ROWS`` save the padding copy made here.
    """
    n_chunks, lanes = u32_lanes.shape
    tile = _tile_for(lanes, tile_lanes)
    n_tiles = -(-lanes // tile)
    n_rows = -(-n_chunks // ROWS) * ROWS
    if widths is None:
        widths = jnp.full((n_chunks,), lanes, jnp.int32)
    w = jnp.pad(widths.astype(jnp.int32).reshape(-1),
                (0, n_rows - widths.size))
    if n_rows != n_chunks or n_tiles * tile != lanes:
        u32_lanes = jnp.pad(u32_lanes, ((0, n_rows - n_chunks),
                                        (0, n_tiles * tile - lanes)))
    part = jax.ShapeDtypeStruct((n_rows, _LANES), jnp.uint32)
    part_spec = pl.BlockSpec((ROWS, _LANES), lambda i, j, w_ref: (i, 0))
    part_x, part_s = pl.pallas_call(
        _fp_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_rows // ROWS, n_tiles),
            in_specs=[pl.BlockSpec((ROWS, tile),
                                   lambda i, j, w_ref: (i, j))],
            out_specs=[part_spec, part_spec]),
        out_shape=[part, part],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="chunk_fingerprint",
    )(w, u32_lanes)
    fp_xor = jax.lax.reduce(part_x[:n_chunks], jnp.uint32(0),
                            jax.lax.bitwise_xor, dimensions=(1,))
    fp_sum = jnp.sum(part_s[:n_chunks], axis=1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(
        jnp.stack([fp_xor, fp_sum], axis=-1), jnp.int32)
