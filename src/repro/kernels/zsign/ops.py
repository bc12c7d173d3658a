"""jit'd public wrappers around the z-sign Pallas kernels.

Handle arbitrary-shaped inputs: the fused encode pads a flat buffer only to
the next 128 lanes (and not at all at a width of whole lanes), the other
kernels to the 8192-element tile.
The kernels run compiled on the TPU and in interpret mode on the CPU
(kernels/common.interpret_mode); interpret mode checks the kernels' logic,
not what the TPU compiler accepts (tests/test_tpu_compile.py does that).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.core import noise as znoise
from repro.core import wire
from repro.kernels.common import (FLAT_ROWS, LANE, ROWS_BLK, TILE,
                                  flat_view, interpret_mode)
from repro.kernels.zsign import zsign as K


def _pad_flat(x: jax.Array, to: int = TILE) -> jax.Array:
    """x -> the flat (-1, LANE) view of its elements, zero-padded to a
    multiple of ``to``: a copy only when the size is not one already."""
    flat = x.reshape(-1)
    pad = (-flat.size) % to
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat_view(flat)


@partial(jax.jit, static_argnames=("interpret",))
def zsign_compress(x: jax.Array, noise: jax.Array, sigma,
                   *, interpret: bool | None = None) -> jax.Array:
    """Fused noisy-sign + bitpack.  x, noise: same shape float32.
    Returns uint8 of ceil(x.size/8) bytes (padded tail packs sign(+pad zeros)).
    """
    interpret = interpret_mode() if interpret is None else interpret
    x2d = _pad_flat(x.astype(jnp.float32))
    n2d = _pad_flat(noise.astype(jnp.float32))
    packed = K.compress_pallas(x2d, n2d, jnp.asarray(sigma), interpret=interpret)
    return packed.reshape(-1)


def _batched_encode_tiles_jnp(x2d, key2, sigma, *, z):
    """Client-batched counter-stream encode, pure jnp, tile-scanned.

    x2d: (n, rows, 128) f32 flat views; key2: (n, 2) u32; sigma: (n,) f32
    -> (n, n_tiles * 8, 128) u8 with n_tiles = ceil(rows / 64),
    byte-for-byte the ``compress_rng_pallas`` output (same global
    quarter-counters, same tile word layout — noise.tile_u01; the rows
    past the buffer encoded as zeros). The lax.scan walks the TILE axis
    so the largest computed f32 intermediate is one (n, 8192) uniform
    window, never an (n, d) noise surface (the jaxpr pin of
    tests/test_encode_fused.py)."""
    n = x2d.shape[0]
    n_tiles = -(-x2d.shape[1] // FLAT_ROWS)
    x2d = jnp.pad(x2d, ((0, 0), (0, n_tiles * FLAT_ROWS - x2d.shape[1]),
                        (0, 0)))
    rows = n_tiles * ROWS_BLK
    if z is None:
        return wire.pack_bool(x2d >= 0.0).reshape(n, rows, LANE)
    k0, k1 = key2[:, 0], key2[:, 1]
    sig = sigma.reshape(n, 1)
    xt = jnp.moveaxis(x2d.reshape(n, n_tiles, TILE), 1, 0)

    def step(_, xs):
        x_t, t = xs                                   # (n, 8192), () u32
        u = jax.vmap(lambda a, b: znoise.tile_u01(a, b, t * TILE, TILE))(
            k0, k1)
        bits = znoise.stochastic_sign_bits(x_t, u, sig, z)
        return None, wire.pack_bool(bits).reshape(n * ROWS_BLK, LANE)

    _, packed = jax.lax.scan(
        step, None, (xt, jnp.arange(n_tiles, dtype=jnp.uint32)))
    # (n_tiles, n*ROWS_BLK, LANE) -> per-client (rows, LANE), tile-major
    return jnp.moveaxis(packed.reshape(n_tiles, n, ROWS_BLK, LANE),
                        1, 0).reshape(n, rows, LANE)


@lru_cache(maxsize=None)
def _rng_encode_vmappable(z, interpret: bool):
    """The pallas_call site of ``zsign_encode_fused`` with a custom vmap
    rule (cached per static (z, interpret) since custom_vmap carries no
    static args).

    JAX's default pallas batching rule appends the mapped client axis to
    the grid, and in interpret mode every grid step then re-materializes
    the whole (n, rows, 128) output via a batched dynamic-update-slice —
    per-client encode cost grows ~linearly with the vmap width (measured
    50 -> 1560 us/client from n=16 to n=256 at d=1024). The rule here
    replaces that lowering wholesale:

      * compiled TPU path: :func:`zsign.compress_rng_pallas` folds
        the client axis into the kernel GRID — block-pipelined in-place
        writes, one kernel launch, linear in n;
      * interpret/CPU path: the tile-scanned jnp twin
        (:func:`_batched_encode_tiles_jnp`) — an interpret-mode grid walks
        its steps sequentially through full-buffer copies, so ANY pallas
        lowering is O(n^2) there; the jnp path is elementwise-linear.

    Both produce each client's unbatched byte stream bit-exactly (global
    counters make the tiling invisible — noise.tile_u01)."""

    @jax.custom_batching.custom_vmap
    def enc(x2d, key2, sigma):
        return K.compress_rng_pallas(x2d[None], key2, sigma.reshape(1), z=z,
                                     interpret=interpret)[0]

    @enc.def_vmap
    def _batched(axis_size, in_batched, x2d, key2, sigma):
        n = axis_size
        if not in_batched[0]:
            x2d = jnp.broadcast_to(x2d[None], (n,) + x2d.shape)
        if not in_batched[1]:
            key2 = jnp.broadcast_to(key2[None], (n,) + key2.shape)
        if not in_batched[2]:
            sigma = jnp.broadcast_to(jnp.reshape(sigma, (1,)), (n,))
        key2 = key2.reshape(n, 2)
        sigma = sigma.reshape(n).astype(jnp.float32)
        if interpret:
            return _batched_encode_tiles_jnp(x2d, key2, sigma, z=z), True
        return K.compress_rng_pallas(x2d, key2, sigma, z=z,
                                     interpret=interpret), True

    return enc


@partial(jax.jit, static_argnames=("z", "add_noise", "interpret"))
def zsign_encode_fused(x: jax.Array, key: jax.Array, sigma,
                       *, z: int, add_noise: bool = True,
                       interpret: bool | None = None) -> jax.Array:
    """Fused client encode with IN-KERNEL counter-based noise.

    x: any-shape float32; key: the client's PRNG key (typed or raw uint32
    pair). The kernel reads x's flat (-1, 128) view, which is free at a
    width of whole lanes (else x is padded to the next 128 only), in blocks
    of many 8192-element tiles with a ragged last block. Each tile derives
    its randomness from threefry2x32(key, global_counters) and writes
    Sign(x + sigma*xi_z) as wire bytes directly — no fp32 noise buffer and
    no tile-padded copy of x in HBM, unlike ``zsign_compress`` which takes a
    dense noise input. Returns uint8 of ceil(x.size/8192)*1024 bytes: the
    wire's whole tiles, the part past x.size encoded as zeros, as
    zsign_compress pads them.
    ``z`` must be Z_INF (uniform) or 1 (Gaussian); ``add_noise=False``
    (static sigma == 0, vanilla SignSGD) skips the PRNG entirely. ``sigma``
    may be traced (Plateau dynamic sigma; stosign's per-client norm) — a
    runtime 0 also degrades exactly to noise-free signs.
    """
    interpret = interpret_mode() if interpret is None else interpret
    x2d = _pad_flat(x.astype(jnp.float32), LANE)
    k0, k1 = znoise.key_words(key)
    key2 = jnp.stack([k0, k1]).reshape(1, 2)
    enc = _rng_encode_vmappable(z if add_noise else None, interpret)
    packed = enc(x2d, key2, jnp.asarray(sigma, jnp.float32))
    return packed.reshape(-1)


@partial(jax.jit, static_argnames=("interpret",))
def sign_reduce(packed: jax.Array, weights: jax.Array,
                acc: jax.Array | None = None,
                *, interpret: bool | None = None) -> jax.Array:
    """Fused weighted sign-reduce: (n_clients, n_bytes) u8 + (n_clients,)
    f32 -> (8*n_bytes,) f32 weighted sum of the +/-1 signs, plus ``acc``.

    ONE kernel launch for the whole client stack (clients folded into the
    grid, the sum resident in VMEM per output block). A stack of fewer than
    CLIENT_BLK clients is one block of its own size; a larger one is padded
    to whole blocks of CLIENT_BLK with zero weight, and bytes to the
    (ROWS_BLK * LANE) tile; both pads contribute exactly 0. A flat
    (8*n_bytes,) f32 ``acc`` is aliased onto the kernel's output and folded
    in first, ``((acc + b_0) + b_1) + ...``: the sum is updated in place,
    with no separate add. The round's wires are whole tiles, so ``acc``
    is only padded (copied) for widths off the tile.
    """
    interpret = interpret_mode() if interpret is None else interpret
    n, nbytes = packed.shape
    bpad = (-nbytes) % (ROWS_BLK * LANE)
    cpad = (-n) % min(n, K.CLIENT_BLK)
    if bpad or cpad:
        packed = jnp.pad(packed, ((0, cpad), (0, bpad)))
    w = weights.astype(jnp.float32)
    if cpad:
        w = jnp.pad(w, (0, cpad))
    if acc is not None:
        acc = flat_view(jnp.pad(acc, (0, 8 * bpad)) if bpad else acc)
    p3 = packed.reshape(n + cpad, -1, LANE)
    s = K.sign_reduce_pallas(p3, w, acc, interpret=interpret)
    return s.reshape(-1)[: nbytes * 8]


@partial(jax.jit, static_argnames=("n_coords", "interpret"))
def zsign_decompress_sum(packed: jax.Array, n_coords: int,
                         *, interpret: bool | None = None) -> jax.Array:
    """packed: (n_clients, n_bytes) uint8 -> (n_coords,) f32 sum of signs:
    the sign-reduce with unit weights."""
    w = jnp.ones((packed.shape[0],), jnp.float32)
    return sign_reduce(packed, w, interpret=interpret)[:n_coords]
