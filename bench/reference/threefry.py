"""Threefry-2x32 with 13 rounds, and the z-sign wire bit, from their
definitions.

Threefry-2x32/R is the block cipher of Salmon et al., "Parallel random
numbers: as easy as 1, 2, 3" (SC'11), as Random123 defines it: key words
(k0, k1) and the parity word k0 ^ k1 ^ 0x1BD11BDA, an initial key
injection, R rounds of add/rotate/xor with the rotation constants
13, 15, 26, 6, 17, 29, 16, 24, and a key injection after every fourth
round.

The z-sign encode of a flat f32 buffer x (the pseudo-gradient, all leaves
concatenated) sends for coordinate i the bit [x_i + sigma * F^{-1}(u_i) >= 0],
which for Gaussian noise (z = 1) is the bit [u_i > 1 - Phi(x_i / sigma)]. The
uniform u_i comes from a counter stream: coordinates are grouped in tiles of
8192, each tile in four quarters of 2048; coordinate i = 8192 t + 2048 j + k
takes counter 2048 t + k, whose cipher words (y0, y1) give four 16-bit halves
[lo(y0), hi(y0), lo(y1), hi(y1)], and quarter j takes half j:
u = (half + 0.5) / 2^16.
"""
from __future__ import annotations

import jax.numpy as jnp

ROUNDS = 13
ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)
PARITY = 0x1BD11BDA
TILE = 8192
QUARTER = TILE // 4


def _rotl(x, r):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1, rounds: int = ROUNDS):
    """Encrypt the counter (c0, c1) under the key (k0, k1): uint32 in, out."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(PARITY))
    x0, x1 = c0 + ks[0], c1 + ks[1]
    for r in range(rounds):
        x0 = x0 + x1
        x1 = _rotl(x1, ROTATIONS[r % 8])
        x1 = x1 ^ x0
        if (r + 1) % 4 == 0:
            s = (r + 1) // 4
            x0 = x0 + ks[s % 3]
            x1 = x1 + ks[(s + 1) % 3] + jnp.uint32(s)
    return x0, x1


def uniforms(key, index):
    """u_i for the global coordinate indices ``index`` (uint32) of the client
    whose key words are ``key`` (a (2,) uint32 array)."""
    index = index.astype(jnp.uint32)
    tile, within = index // TILE, index % TILE
    quarter, k = within // QUARTER, within % QUARTER
    y0, y1 = threefry2x32(key[0], key[1], tile * QUARTER + k,
                          jnp.zeros_like(index))
    word = jnp.where(quarter < 2, y0, y1)
    half = jnp.where(quarter % 2 == 0, word & jnp.uint32(0xFFFF),
                     word >> jnp.uint32(16))
    return (half.astype(jnp.float32) + 0.5) * jnp.float32(2.0 ** -16)
