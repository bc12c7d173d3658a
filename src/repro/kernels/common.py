"""What every Pallas kernel here shares: the tile geometry, the wire bit-pack,
and the switch between compiled and interpreted kernels.

Wire layout: element 8i+j of a flat buffer is bit j of byte i. A kernel tile
is (ROWS_BLK, COLS) f32 -> (ROWS_BLK, LANE) uint8, so byte b of a row holds
that row's elements 8b..8b+7.

f32 operands cross HBM as (n * FLAT_ROWS, LANE) arrays: on the TPU that
shape has the same physical layout as the flat (n * TILE,) buffer, so the
reshape between them is free, while a (n * ROWS_BLK, COLS) array would be a
relayout copy of the whole buffer. The kernel reshapes each (FLAT_ROWS,
LANE) block to its (ROWS_BLK, COLS) tile in VMEM (:func:`to_tile`).

The TPU compiler (Mosaic) cannot split a lane into (LANE, PACK) or reduce
over unsigned integers, so the pack and the unpack run on the MXU instead:

  pack    bits (R, 1024) in {0,1} @ PACK_MATRIX (1024, 128), which holds
          2^j at [8i+j, i] -> the byte values 0..255, exact in f32;
  unpack  bytes (R, 128) @ SPREAD_MATRIX (128, 1024), which holds 1 at
          [i, 8i+j] -> byte i copied to its 8 lanes, then bit (lane % 8).

bf16 holds 0/1, the powers 2^0..2^7 and every byte value exactly, and the
products accumulate in f32, so both directions are exact on every backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANE = 128
PACK = 8
COLS = LANE * PACK          # 1024 elements per row
ROWS_BLK = 8                # 8192 elements per tile
TILE = ROWS_BLK * COLS
FLAT_ROWS = TILE // LANE    # 64: rows of one tile in the flat (.., LANE) view


def interpret_mode() -> bool:
    """Compiled kernels on the TPU, interpreted kernels on the CPU, and an
    error on any other backend (never a silent fallback)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and are interpreted on the CPU "
        f"only; the default backend is {backend!r}")


def pack_matrix() -> jax.Array:
    """(COLS, LANE) bf16 with 2^j at [8i+j, i]."""
    r = np.arange(COLS)
    m = np.zeros((COLS, LANE), np.float32)
    m[r, r // PACK] = 2.0 ** (r % PACK)
    return jnp.asarray(m, jnp.bfloat16)


def spread_matrix() -> jax.Array:
    """(LANE, COLS) bf16 with 1 at [i, 8i+j]."""
    c = np.arange(COLS)
    m = np.zeros((LANE, COLS), np.float32)
    m[c // PACK, c] = 1.0
    return jnp.asarray(m, jnp.bfloat16)


def flat_view(flat: jax.Array) -> jax.Array:
    """(n * TILE,) f32 -> (n * FLAT_ROWS, LANE), free on the TPU."""
    return flat.reshape(-1, LANE)


def flat_spec(index_map):
    """BlockSpec of one tile of a flat-view f32 operand."""
    return pl.BlockSpec((FLAT_ROWS, LANE), index_map)


def to_tile(block: jax.Array) -> jax.Array:
    """(t * FLAT_ROWS, LANE) -> (t * ROWS_BLK, COLS), the same elements in
    order, for a block of t tiles."""
    return block.reshape(-1, COLS)


def from_tile(tile: jax.Array) -> jax.Array:
    """(t * ROWS_BLK, COLS) -> (t * FLAT_ROWS, LANE), the inverse of
    :func:`to_tile`."""
    return tile.reshape(-1, LANE)


def matrix_spec(shape):
    """BlockSpec of a constant matrix operand: the same block at every grid
    step, so it is copied into VMEM once."""
    return pl.BlockSpec(shape, lambda *_: (0, 0))


def pack_bits(bits: jax.Array, pack_m: jax.Array) -> jax.Array:
    """(R, COLS) bool -> (R, LANE) uint8 in the wire layout."""
    ones = jnp.where(bits, 1.0, 0.0).astype(jnp.bfloat16)
    v = jnp.dot(ones, pack_m, preferred_element_type=jnp.float32)
    return v.astype(jnp.int32).astype(jnp.uint8)


def unpack_bits(packed: jax.Array, spread_m: jax.Array) -> jax.Array:
    """(R, LANE) uint8 -> (R, COLS) bool, the inverse of :func:`pack_bits`."""
    b = packed.astype(jnp.int32).astype(jnp.bfloat16)
    v = jnp.dot(b, spread_m, preferred_element_type=jnp.float32)
    v = v.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1) & (PACK - 1)
    return ((v >> lane) & 1) > 0
