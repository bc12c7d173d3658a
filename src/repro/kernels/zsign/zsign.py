"""Pallas TPU kernels for z-SignFedAvg's compression hot path.

Three kernels:

  _compress_kernel:  y = x + sigma*noise; pack Sign(y) bits -> uint8
                     (fused elementwise + 8:1 bitpack; 1 byte out per 8 in;
                     noise is a kernel INPUT — the dense-noise path, kept for
                     finite z > 1 and as the reference encoder)
  _compress_rng_kernel: in-kernel counter-based noise — each grid tile
                     derives its randomness from threefry2x32(client_key,
                     tile_counters) (core/noise.py, plain VPU uint32 ops; 4
                     u16 uniforms per call) and samples the wire bit
                     directly from its exact Bernoulli law
                     [u > 1 - P_z(x/sigma)] (the inverse-CDF coupling of
                     noise.stochastic_sign_bits). The fp32 noise buffer
                     never exists: the client encode reads x and writes wire
                     bytes, nothing else. Counters are GLOBAL quarter-tile
                     indices, so the jnp encode (core/compression.py)
                     reproduces the byte stream bit-exactly. The client axis
                     is the outer grid axis: one launch encodes a whole
                     client stack.
  _sign_reduce_kernel: (n_clients, ...) packed uint8 + (n_clients,) fp32
                     weights [+ the carried fp32 sum] -> that sum plus the
                     weighted sum of {-1,+1} fp32, with the client axis
                     folded into the grid and the output block resident in
                     VMEM while every client block streams past it. This is
                     the fused server aggregation: the dense (n_clients, d)
                     fp32 sign matrix never exists. A client block is the
                     whole stack under CLIENT_BLK clients (no dead rows),
                     else CLIENT_BLK; a grid step covers as many tiles as
                     REDUCE_VMEM allows, so the MXU unpack runs on
                     (8 * tiles, 128) bytes at once. The carried sum is
                     aliased onto the output and added at the first client
                     block: a streamed fold reads and writes the sum once
                     per call, in place.

The encode kernels stream HBM->VMEM one 8192-element f32 tile per grid
step (a (64, 128) block of the flat view, worked on as (8, 1024)) and write
(8, 128) uint8 tiles; the reduce takes a run of such tiles per step. The
layout, the bit-pack and the unpack are the shared forms of
kernels/common.py. Per-client scalars (key words, sigma, the
threshold scale, reduce weights) live in SMEM; the tile index is the grid
position. The counter scheme was chosen over pltpu.prng_random_bits because
the hardware PRNG's stream cannot be reproduced off-TPU — threefry2x32 is
~13 VPU integer ops per word and gives the interpret-mode kernel, the
compiled TPU kernel, and the jnp encode the identical byte stream for the
same client key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import noise as znoise
from repro.kernels.common import (COLS, FLAT_ROWS, LANE, ROWS_BLK, TILE,
                                  flat_spec, from_tile, matrix_spec, pack_bits,
                                  pack_matrix, spread_matrix, to_tile,
                                  unpack_bits)

CLIENT_BLK = 8              # clients per sign-reduce block, stacks of 8+
REDUCE_VMEM = 8 << 20       # VMEM budget of one sign-reduce grid step

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _compress_kernel(sig_ref, x_ref, n_ref, m_ref, o_ref):
    y = to_tile(x_ref[...]) + sig_ref[0] * to_tile(n_ref[...])
    o_ref[...] = pack_bits(y >= 0.0, m_ref[...])


def compress_pallas(x2d: jax.Array, noise2d: jax.Array, sigma: jax.Array,
                    *, interpret: bool) -> jax.Array:
    """x2d/noise2d: flat views (n_tiles * 64, 128) f32 ->
    (n_tiles * 8, 128) u8."""
    n_tiles = x2d.shape[0] // FLAT_ROWS
    tile = flat_spec(lambda i: (i, 0))
    return pl.pallas_call(
        _compress_kernel,
        grid=(n_tiles,),
        in_specs=[_SMEM, tile, tile, matrix_spec((COLS, LANE))],
        out_specs=pl.BlockSpec((ROWS_BLK, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * ROWS_BLK, LANE), jnp.uint8),
        name="zsign_compress",
        interpret=interpret,
    )(sigma.reshape(1).astype(jnp.float32), x2d, noise2d, pack_matrix())


def _compress_rng_kernel(k_ref, sig_ref, inv_ref, x_ref, m_ref, o_ref, *, z):
    """Counter-based in-kernel noise: one tile of one client's encode.

    Grid (client c, tile t). Tile t covers the client's elements
    [t*8192, (t+1)*8192). Quarter-counters are global (c = t*2048 + local);
    one threefry2x32 call yields 4 u16 uniforms that feed the tile's four
    row-quarters — the layout of noise.tile_u01, which the jnp encode
    replays verbatim. ``z`` is static: None disables the noise entirely
    (vanilla SignSGD, sigma == 0), else z in {Z_INF, 1} selects the sign
    CDF.
    """
    def plain():
        o_ref[...] = pack_bits(to_tile(x_ref[...]) >= 0.0, m_ref[...])

    if z is None:
        plain()
        return
    c = pl.program_id(0)
    t = pl.program_id(1).astype(jnp.uint32)

    @pl.when(sig_ref[c] > 0.0)
    def _noisy():
        qrows = ROWS_BLK // 4
        row = jax.lax.broadcasted_iota(jnp.uint32, (qrows, COLS), 0)
        col = jax.lax.broadcasted_iota(jnp.uint32, (qrows, COLS), 1)
        cnt = t * jnp.uint32(TILE // 4) + row * jnp.uint32(COLS) + col
        y0, y1 = znoise.counter_words(k_ref[2 * c], k_ref[2 * c + 1], cnt)
        u0, u1 = znoise.halves_to_u01(y0)
        u2, u3 = znoise.halves_to_u01(y1)
        u = jnp.concatenate([u0, u1, u2, u3], axis=0)  # (R, 1024) in (0,1)
        bits = znoise.noisy_sign_bits(to_tile(x_ref[...]), u, inv_ref[c], z)
        o_ref[...] = pack_bits(bits, m_ref[...])

    # a runtime sigma of 0 is the noise-free sign (stochastic_sign_bits)
    pl.when(sig_ref[c] <= 0.0)(plain)


def compress_rng_pallas(x2d: jax.Array, key2: jax.Array, sigma: jax.Array,
                        *, z, interpret: bool) -> jax.Array:
    """Fused encode of a client stack, the client axis folded into the GRID.

    x2d: flat view (n * n_tiles * 64, 128) f32 of n clients' tile-padded
    buffers stacked contiguously; key2: (n, 2) uint32; sigma: (n,) f32 ->
    (n * n_tiles * 8, 128) u8.

    Every client sees exactly the counter stream of a one-client call (its
    tile index is the grid position along its own rows), so the bytes are
    bit-identical for any n. Folding the batch into the grid (instead of
    letting vmap batch the pallas_call) keeps each grid step's output write
    loop-indexed: JAX's pallas batching rule would instead add the client
    axis to every dynamic-update-slice, which XLA lowers to a per-tile copy
    of the WHOLE (n, rows, 128) buffer.
    """
    n = key2.shape[0]
    n_tiles = x2d.shape[0] // n // FLAT_ROWS
    sigma = sigma.reshape(n).astype(jnp.float32)
    inv = (jnp.zeros_like(sigma) if z is None
           else znoise.threshold_scale(sigma, z))
    return pl.pallas_call(
        functools.partial(_compress_rng_kernel, z=z),
        grid=(n, n_tiles),
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            flat_spec(lambda c, i: (c * n_tiles + i, 0)),
            matrix_spec((COLS, LANE)),
        ],
        out_specs=pl.BlockSpec((ROWS_BLK, LANE),
                               lambda c, i: (c * n_tiles + i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * n_tiles * ROWS_BLK, LANE),
                                       jnp.uint8),
        name="compress_rng",
        interpret=interpret,
    )(key2.reshape(-1).astype(jnp.uint32), sigma, inv, x2d, pack_matrix())


def reduce_tiles(n_tiles: int, blk: int) -> int:
    """Tiles per sign-reduce grid step for a client block of ``blk`` rows.

    The most that fit REDUCE_VMEM, rounded down to a power of two: the
    double-buffered f32 accumulator and output blocks and ``blk`` packed
    rows per tile, plus about three f32 tiles of temporaries. All of a
    shorter buffer is one block."""
    per_tile = 2 * (2 * 4 * TILE + blk * TILE // 8) + 3 * 4 * TILE
    t = 1 << ((REDUCE_VMEM // per_tile).bit_length() - 1)
    return n_tiles if n_tiles <= t else t


def _sign_reduce_kernel(w_ref, p_ref, e_ref, *refs, blk):
    a_ref, o_ref = refs if len(refs) == 2 else (None, refs[0])
    c = pl.program_id(1)
    spread = e_ref[...]
    part = None
    for j in range(blk):                             # left fold, client order
        w = w_ref[c * blk + j]
        term = jnp.where(unpack_bits(p_ref[j], spread), w, -w)
        part = term if part is None else part + term

    part = from_tile(part)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = part if a_ref is None else a_ref[...] + part

    @pl.when(c != 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


def sign_reduce_pallas(packed: jax.Array, weights: jax.Array,
                       acc: jax.Array | None = None,
                       *, interpret: bool) -> jax.Array:
    """packed: (n_clients, n_tiles * 8, 128) u8, weights: (n_clients,) f32,
    acc: None or a flat view (n_tiles * 64, 128) f32 -> the flat view of
    ``acc`` plus the weighted sum of signs.

    The client block is the whole stack when it has fewer than CLIENT_BLK
    rows, else CLIENT_BLK rows, and n_clients must be a multiple of it
    (the caller pads with weight-0 rows, which contribute exactly 0). The
    client axis is the INNER grid dimension, so each output block stays
    resident in VMEM while every client block streams past it; each step
    covers :func:`reduce_tiles` tiles, the last block ragged. ``acc`` is
    aliased onto the output and folded in first, in place:
    ``((acc + b_0) + b_1) + ...`` over the client blocks, the order of
    ``wire.unpack_sum``.
    """
    n, rows, _ = packed.shape
    n_tiles = rows // ROWS_BLK
    blk = min(n, CLIENT_BLK)
    t = reduce_tiles(n_tiles, blk)
    out_spec = pl.BlockSpec((t * FLAT_ROWS, LANE), lambda i, c: (i, 0))
    operands = [weights.reshape(n).astype(jnp.float32), packed,
                spread_matrix()]
    in_specs = [
        _SMEM,
        pl.BlockSpec((blk, t * ROWS_BLK, LANE), lambda i, c: (c, i, 0)),
        matrix_spec((LANE, COLS)),
    ]
    if acc is not None:
        operands.append(acc)
        in_specs.append(out_spec)
    return pl.pallas_call(
        functools.partial(_sign_reduce_kernel, blk=blk),
        grid=(pl.cdiv(n_tiles, t), n // blk),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * FLAT_ROWS, LANE),
                                       jnp.float32),
        input_output_aliases={} if acc is None else {3: 0},
        name="sign_reduce",
        interpret=interpret,
    )(*operands)
