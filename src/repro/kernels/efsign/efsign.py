"""Pallas TPU kernel: fused EF-SignSGD compress + residual update.

One pass over HBM computes ALL THREE outputs of the error-feedback step —
q = scale*Sign(g+e), the new residual e' = g+e-q, and the bitpacked uint8
wire payload (bit j of byte i == Sign((g+e)[8i+j]) >= 0, the same
little-endian layout as kernels/zsign) — instead of the separate elementwise
+ pack passes the naive jnp formulation costs. Same tiling as kernels/zsign: one 8192-element fp32 tile of
the flat view in, (8, 128) uint8 payload tiles out, with the shared layout
and bit-pack of kernels/common.py.

Sign convention is ``p >= 0 -> +1`` (matching wire.pack_flat), NOT jnp.sign:
the residual must account exactly for what the server decodes from the
bitpacked payload, including p == 0 coordinates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (COLS, FLAT_ROWS, LANE, ROWS_BLK, flat_spec,
                                  matrix_spec, pack_bits, pack_matrix, to_tile)


def _ef_kernel(s_ref, g_ref, e_ref, m_ref, q_ref, eout_ref, p_ref):
    p = g_ref[...] + e_ref[...]
    s = s_ref[0]
    q = jnp.where(p >= 0.0, s, -s)
    q_ref[...] = q
    eout_ref[...] = p - q
    p_ref[...] = pack_bits(to_tile(p) >= 0.0, m_ref[...])


def ef_update_pallas(g2d, e2d, scale, *, interpret: bool):
    """flat views (n_tiles * 64, 128) f32 x2 + scale ->
    (q, e_new, packed_u8[n_tiles * 8, 128]), q and e_new as flat views."""
    n_tiles = g2d.shape[0] // FLAT_ROWS
    rows = n_tiles * FLAT_ROWS
    tile = flat_spec(lambda i: (i, 0))
    return pl.pallas_call(
        _ef_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile,
                  matrix_spec((COLS, LANE))],
        out_specs=[tile, tile,
                   pl.BlockSpec((ROWS_BLK, LANE), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * ROWS_BLK, LANE), jnp.uint8),
        ],
        name="ef_update",
        interpret=interpret,
    )(scale.reshape(1).astype(jnp.float32), g2d, e2d, pack_matrix())
