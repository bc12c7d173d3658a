"""Pallas TPU kernels for z-SignFedAvg's compression hot path.

Three kernels:

  _compress_kernel:  y = x + sigma*noise; pack Sign(y) bits -> uint8
                     (fused elementwise + 8:1 bitpack; 1 byte out per 8 in;
                     noise is a kernel INPUT — the dense-noise path, kept for
                     finite z > 1 and as the reference encoder)
  _compress_rng_kernel: in-kernel counter-based noise — each tile
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

The dense-noise encode streams HBM->VMEM one 8192-element f32 tile per grid
step (a (64, 128) block of the flat view, worked on as (8, 1024)) and writes
(8, 128) uint8 tiles. The counter encode takes a block of many tiles a step
(:func:`encode_tiles`) straight from the client's unpadded flat (rows, 128)
view, its last block ragged, and works in the flat view on full vregs:
threefry runs on one (16, 128) counter array a tile, and only the bits go
to the (8, 1024) tile view, for the MXU pack. The reduce takes a run of
tiles per step. The layout, the bit-pack and the unpack are the shared
forms of kernels/common.py. Per-client scalars (key words, sigma, the
threshold scale, reduce weights) live in SMEM; the tile index follows from
the grid position. The counter scheme was chosen over
pltpu.prng_random_bits because the hardware PRNG's stream cannot be
reproduced off-TPU — threefry2x32 is ~13 VPU integer ops per word and gives
the interpret-mode kernel, the compiled TPU kernel, and the jnp encode the
identical byte stream for the same client key.
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
ENCODE_VMEM = 8 << 20       # VMEM budget of one encode grid step's blocks
ENCODE_GROUP = 16           # tiles an encode step computes at a time

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


def encode_tiles(n_tiles: int) -> int:
    """Tiles per encode grid step for a client buffer of ``n_tiles`` tiles.

    The most that fit ENCODE_VMEM, rounded down to a power of two: the
    double-buffered f32 input block and packed output block per tile. The
    step's temporaries are those of one ENCODE_GROUP of tiles, whatever
    the block. All of a shorter buffer is one block."""
    per_tile = 2 * (4 * TILE + TILE // 8)
    t = 1 << ((ENCODE_VMEM // per_tile).bit_length() - 1)
    return n_tiles if n_tiles <= t else t


def _compress_rng_kernel(k_ref, sig_ref, inv_ref, x_ref, m_ref, o_ref, *, z,
                         rows):
    """Counter-based in-kernel noise: one block of one client's encode.

    Grid (client c, block i). The block is ``tiles`` 8192-element tiles of
    the client's flat (rows, 128) view, (tiles * 64, 128) f32, walked in
    groups of up to ENCODE_GROUP tiles. Quarter q of tile t is flat rows
    16q .. 16q + 15 of the tile's (64, 128) rows, and its element at
    (16q + r, lane) takes the global quarter-counter t * 2048 + 128 r +
    lane: so a group of g tiles from tile t0 needs the one (16 g, 128)
    counter array t0 * 2048 + 128 * row + lane, on full vregs. One
    threefry2x32 call per counter yields 4 u16 uniforms, one for each
    quarter: the layout of noise.tile_u01, which the jnp encode replays
    verbatim. Only the 0/1 bits go to the (8 g, 1024) tile view, for the
    MXU pack. Rows past ``rows`` (the ragged last block) read as 0, which
    is what a zero pad of the buffer gave. ``z`` is static: None disables
    the noise entirely (vanilla SignSGD, sigma == 0), else z in {Z_INF, 1}
    selects the sign CDF.
    """
    c = pl.program_id(0)
    blk_rows = x_ref.shape[0]
    tiles = blk_rows // FLAT_ROWS
    row0 = pl.program_id(1) * blk_rows
    qrows = FLAT_ROWS // 4
    pack_m = m_ref[...]

    def plain(x, t0, g):
        return x >= 0.0

    def noisy(x, t0, g):
        row = jax.lax.broadcasted_iota(jnp.uint32, (g * qrows, LANE), 0)
        lane = jax.lax.broadcasted_iota(jnp.uint32, (g * qrows, LANE), 1)
        cnt = (t0.astype(jnp.uint32) * jnp.uint32(TILE // 4)
               + row * jnp.uint32(LANE) + lane)
        y0, y1 = znoise.counter_words(k_ref[2 * c], k_ref[2 * c + 1], cnt)
        us = znoise.halves_to_u01(y0) + znoise.halves_to_u01(y1)
        xq = x.reshape(g, 4, qrows, LANE)
        return jnp.stack(
            [znoise.noisy_sign_bits(xq[:, q], u.reshape(g, qrows, LANE),
                                    inv_ref[c], z)
             for q, u in enumerate(us)], axis=1).reshape(g * FLAT_ROWS, LANE)

    def encode(sign_bits):
        def group(j, g):
            r = pl.multiple_of(j * FLAT_ROWS, FLAT_ROWS)
            x = x_ref[pl.ds(r, g * FLAT_ROWS), :]
            if rows % blk_rows:                       # a ragged last block
                at = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
                x = jnp.where(at < rows - row0 - r, x, 0.0)
            ones = jnp.where(sign_bits(x, row0 // FLAT_ROWS + j, g), 1.0, 0.0)
            o_ref[pl.ds(pl.multiple_of(j * ROWS_BLK, ROWS_BLK),
                        g * ROWS_BLK), :] = pack_bits(to_tile(ones), pack_m)

        full, tail = divmod(tiles, ENCODE_GROUP)
        if full:
            pl.loop(0, full)(lambda k: group(k * ENCODE_GROUP, ENCODE_GROUP))
        if tail:
            group(full * ENCODE_GROUP, tail)

    if z is None:
        encode(plain)
        return

    @pl.when(sig_ref[c] > 0.0)
    def _noisy():
        encode(noisy)

    # a runtime sigma of 0 is the noise-free sign (stochastic_sign_bits)
    pl.when(sig_ref[c] <= 0.0)(lambda: encode(plain))


def compress_rng_pallas(x3d: jax.Array, key2: jax.Array, sigma: jax.Array,
                        *, z, interpret: bool,
                        tiles: int | None = None) -> jax.Array:
    """Fused encode of a client stack, the client axis folded into the GRID.

    x3d: (n, rows, 128) f32, each client's flat view (rows * 128 >= d
    coordinates, zero past d); key2: (n, 2) uint32; sigma: (n,) f32 ->
    (n, n_tiles * 8, 128) u8, n_tiles = ceil(rows / 64): whole 8192-element
    tiles on the wire, the part past ``rows`` encoded as zeros.

    Each grid step encodes ``tiles`` tiles of one client (by default
    :func:`encode_tiles` of the buffer), the last block ragged, so no
    client buffer is padded to whole tiles or blocks. Every client sees
    exactly the counter stream of a one-client call (its tile index is its
    position along its own rows), so the bytes are bit-identical for any n
    and any ``tiles``. Folding the batch into the grid (instead of letting
    vmap batch the pallas_call) keeps each grid step's output write
    loop-indexed: JAX's pallas batching rule would instead add the client
    axis to every dynamic-update-slice, which XLA lowers to a per-tile copy
    of the WHOLE (n, rows, 128) buffer.
    """
    n, rows, _ = x3d.shape
    n_tiles = pl.cdiv(rows, FLAT_ROWS)
    t = encode_tiles(n_tiles) if tiles is None else min(tiles, n_tiles)
    sigma = sigma.reshape(n).astype(jnp.float32)
    inv = (jnp.zeros_like(sigma) if z is None
           else znoise.threshold_scale(sigma, z))
    return pl.pallas_call(
        functools.partial(_compress_rng_kernel, z=z, rows=rows),
        grid=(n, pl.cdiv(n_tiles, t)),
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec((None, t * FLAT_ROWS, LANE), lambda c, i: (c, i, 0)),
            matrix_spec((COLS, LANE)),
        ],
        out_specs=pl.BlockSpec((None, t * ROWS_BLK, LANE),
                               lambda c, i: (c, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * ROWS_BLK, LANE),
                                       jnp.uint8),
        name="compress_rng",
        interpret=interpret,
    )(key2.reshape(-1).astype(jnp.uint32), sigma, inv, x3d, pack_matrix())


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
