"""z-distribution noise (Definition 1 of the paper).

p_z(t) = exp(-t^{2z}/2) / (2*eta_z),   eta_z = 2^{1/(2z)} * Gamma(1 + 1/(2z))

z=1   -> standard Gaussian.
z=inf -> Uniform[-1, 1]  (Lemma 2), with eta_inf = 1.

Sampling for finite z uses the fact that |xi_z|^{2z} ~ Gamma(shape=1/(2z),
scale=2)^... more precisely if U ~ Gamma(k=1/(2z), theta=2) then U^{1/(2z)}
with a random sign follows p_z:  p_{|xi|}(t) ∝ exp(-t^{2z}/2) on t>=0 and the
change of variables u = t^{2z} gives the Gamma density with shape 1/(2z),
scale 2.

Counter-based stream (the fused client-encode path)
---------------------------------------------------
``sample_z_noise`` draws through jax.random, which is fine when the noise
buffer may exist densely. The fused encode path (kernels/zsign +
core/compression) instead derives every random word from a COUNTER: word i of
client k is ``threefry2x32(key_k, i)``, so any tile/chunk of the stream can
be generated independently inside a Pallas grid step or a jnp chunk, with no
state and no (n_clients, d) noise buffer anywhere. Everything below
``threefry2x32`` is written in plain uint32/f32 jnp ops that lower identically
inside a Pallas TPU kernel and in ordinary XLA, which is what makes the
interpret-mode kernel and the jnp fallback bit-exact against each other.

Bit-transforms (uint32 -> noise):
  ``halves_to_u01``    word -> TWO u in (0,1): the centered 16-bit open
                       uniforms of the word's low/high halves. One
                       threefry2x32 call therefore feeds FOUR coordinates,
                       which is what makes the counter stream cheaper than
                       the jax.random draw it replaces. 16-bit resolution
                       quantizes each wire bit's Bernoulli probability by at
                       most 2^-16 ~ 1.5e-5 — orders of magnitude below the
                       estimator's own Lemma-1 bias at any practical sigma,
                       and invisible to the distribution tests.
  ``u01_to_noise``     u -> xi = F_z^{-1}(u): 2u-1 ~ Uniform(-1,1) for
                       z=inf; sqrt(2)*erfinv(2u-1) ~ N(0,1) for z=1 (the
                       inverse CDF). Box-Muller was measured first and
                       rejected: its cos/sin lower to scalar libm calls on
                       XLA CPU (~5x the cost of the threefry itself);
                       erfinv is the vectorized polynomial jax.random.normal
                       itself uses.
  Finite z > 1 has no cheap inverse CDF -> callers fall back to the dense
  ``sample_z_noise`` path (``counter_supported``).

The encoder never materializes xi at all: Sign(x + sigma*F_z^{-1}(u)) ==
[u > 1 - P_z(x/sigma)] for the symmetric z-noise CDF F_z (P_z(r) =
P(r + xi >= 0) = F_z(r), ``noisy_sign_bits``), so the fused kernels sample the
wire bit directly from its exact Bernoulli law — the inverse-CDF coupling
makes this THE SAME random variable as adding counter noise and taking the
sign, not an approximation (``stochastic_sign_bits``; equivalence verified
in tests/test_encode_fused.py).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Z_INF = 0  # sentinel for z = +inf (uniform noise). Any z <= 0 means infinity.

#: Threefry-2x32 rounds. 13 is the smallest count that passes BigCrush for
#: this variant (Salmon et al., "Parallel random numbers: as easy as 1, 2, 3",
#: SC'11, Table 2); jax's own PRNG uses the conservative 20.
THREEFRY_ROUNDS = 13

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_TINY = 1e-30  # safe-division floor for dynamic sigma == 0


def counter_supported(z: int) -> bool:
    """True iff the counter-based fused encode covers this z (inf or 1)."""
    return z <= Z_INF or z == 1


def client_keys(key: jax.Array, start, n: int) -> jax.Array:
    """Per-client PRNG keys by GLOBAL client index: key_j = fold_in(key, j)
    for j in [start, start + n).

    Counter-based like everything else on the encode path: client j's key
    depends only on j, never on how the round driver partitions the cohort,
    so the streaming shard scan (which derives each shard's keys from its
    global offset) and the all-clients vmap path consume IDENTICAL
    randomness — the bit-identity contract of core/fedavg.py. ``start`` may
    be a traced uint32 scalar (the shard offset inside ``lax.scan``).
    Accepts typed or raw uint32 keys and returns the same flavour, stacked
    on a leading (n,) axis.
    """
    idx = jnp.asarray(start, jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


def key_words(key: jax.Array):
    """PRNG key -> (k0, k1) uint32 scalar words (accepts typed or raw keys)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    key = key.astype(jnp.uint32)
    return key[..., 0], key[..., 1]


def threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32 block cipher: key (k0,k1), counter (x0,x1) -> 2 words.

    Canonical Random123 round structure: initial key injection, then rounds
    in groups of four with a subkey injection after each COMPLETED group
    (a trailing partial group, as with the 13-round variant, ends without
    an injection — matching the reference implementation's unrolling, so
    the stream is exactly the published Threefry-2x32/R).

    Plain uint32 add/xor/rotate jnp ops only, so the SAME function body runs
    inside a Pallas TPU kernel (VPU integer ops) and in ordinary jnp — the
    property the encode-equivalence tests rely on. All inputs must already be
    uint32 (scalars or broadcast-compatible arrays).
    """
    u32 = jnp.uint32
    ks2 = k0 ^ k1 ^ u32(0x1BD11BDA)
    x0 = x0 + k0
    x1 = x1 + k1
    ks = (k1, ks2, k0)
    r_idx = 0
    for i in range(5):
        group = min(4, THREEFRY_ROUNDS - r_idx)
        for _ in range(group):
            r = _ROT[r_idx % 8]
            x0 = x0 + x1
            x1 = (x1 << r) | (x1 >> (32 - r))
            x1 = x1 ^ x0
            r_idx += 1
        if group < 4:
            break
        x0 = x0 + ks[i % 3]
        x1 = x1 + ks[(i + 1) % 3] + u32(i + 1)
        if r_idx >= THREEFRY_ROUNDS:
            break
    return x0, x1


def halves_to_u01(bits):
    """uint32 word -> (u_lo, u_hi), two centered 16-bit open uniforms.

    u = (half + 0.5) / 2^16 is open at both ends (never exactly 0 or 1) and
    exactly symmetric around 1/2, so erfinv(2u-1) is always finite and 2u-1
    has mean exactly 0.
    """
    # via int32: Mosaic has no uint32 -> float32 conversion, and both
    # halves are < 2^16, so the int32 detour is exact
    scale = jnp.float32(2.0 ** -16)
    lo = ((bits & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
          + 0.5) * scale
    hi = ((bits >> 16).astype(jnp.int32).astype(jnp.float32) + 0.5) * scale
    return lo, hi


def u01_to_noise(u, z: int):
    """u in (0,1) -> xi = F_z^{-1}(u), the z-noise inverse CDF (z=inf or 1)."""
    xi = 2.0 * u - 1.0
    if z == 1:
        return jnp.float32(math.sqrt(2.0)) * jax.lax.erf_inv(xi)
    if z <= Z_INF:
        return xi
    raise ValueError(f"u01_to_noise covers z=inf and z=1 only, got {z}")


def counter_words(k0, k1, idx):
    """Quarter-counter array idx -> (y0, y1): 2 words = 4 u16 per counter."""
    return threefry2x32(k0, k1, idx.astype(jnp.uint32), jnp.zeros_like(idx, jnp.uint32))


def tile_u01(k0, k1, start, tile):
    """The canonical tile stream: u01 values for elements
    [start, start + tile) of client (k0,k1)'s sequence, as a flat (tile,)
    f32 array laid out in four quarters:

        [lo16(y0) | hi16(y0) | lo16(y1) | hi16(y1)],   each of tile/4,

    where (y0, y1) = threefry2x32(key, c) over the GLOBAL quarter-counters
    c = start/4 + [0, tile/4). Because the counters are global, any tiling
    of the coordinate axis — Pallas grid steps, jnp chunks, or one single
    pass — produces the identical stream; ``start`` must be a multiple of 4.
    """
    q = tile // 4
    c = jnp.uint32(start) // 4 + jax.lax.iota(jnp.uint32, q)
    y0, y1 = counter_words(k0, k1, c)
    u0, u1 = halves_to_u01(y0)
    u2, u3 = halves_to_u01(y1)
    return jnp.concatenate([u0, u1, u2, u3])


def counter_noise(key, n: int, z: int, *, tile: int = 8192) -> jax.Array:
    """(n,) z-noise values from the counter stream (F_z^{-1} of tile_u01).

    The dense-materializing view of the stream the fused encode consumes —
    used by the distribution/equivalence tests and available as a drop-in for
    ``sample_z_noise`` when bit-reproducible tiled sampling matters. ``n``
    is padded up to ``tile``; pass the same tile the encoder uses (the
    8192-element kernel tile) to reproduce its stream exactly.
    """
    if not counter_supported(z):
        raise ValueError(f"counter stream covers z=inf and z=1 only, got {z}")
    k0, k1 = key_words(key)
    n_tiles = -(-n // tile)
    u = jax.vmap(lambda t: tile_u01(k0, k1, t * tile, tile))(
        jnp.arange(n_tiles, dtype=jnp.uint32)).reshape(-1)
    return u01_to_noise(u, z)[:n]


# The f32 erf approximation erf(y) ~ y * A(y^2) / B(y^2) on y in [-4, 4]
# (the rational form XLA lowers lax.erf to), as Horner coefficients from the
# highest degree down. Both polynomials are negative on the whole range.
_ERF_A = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_B = (-1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
          -7.37332916720468e-03, -1.42647390514189e-02)


def _horner(y2, coeffs):
    acc = jnp.full_like(y2, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * y2 + jnp.float32(c)
    return acc


def threshold_scale(sigma, z: int) -> jax.Array:
    """The per-client multiplier that maps a coordinate x to the argument of
    the sign CDF: 1/sigma for z=inf, 1/(sigma*sqrt(2)) for z=1 (the erf
    argument). Computed once per client OUTSIDE the encode kernels, so the
    one f32 division of the encode is the same XLA op on every backend."""
    sig = jnp.maximum(jnp.asarray(sigma, jnp.float32), _TINY)
    if z == 1:
        sig = sig * jnp.float32(math.sqrt(2.0))
    return 1.0 / sig


def noisy_sign_bits(x, u, inv, z: int):
    """[u > 1 - P_z(x * inv * c_z)] with ``inv = threshold_scale(sigma, z)``:
    the z-sign wire bit for sigma > 0.

    Only f32 multiply, add, clamp and compare: no division, no
    transcendental. That keeps the bit identical between the Pallas kernel
    (Mosaic) and ordinary XLA on the same chip. For z=1 the comparison
    erf(y) > 1 - 2u is cross-multiplied by the (negative) denominator of the
    rational erf, so it needs no division either.
    """
    if z <= Z_INF:
        return u > 1.0 - jnp.clip(0.5 * (x * inv + 1.0), 0.0, 1.0)
    if z == 1:
        y = jnp.clip(x * inv, -4.0, 4.0)
        y2 = y * y
        # erf(y) = y*A/B > t  <=>  y*A < t*B, since B < 0
        return y * _horner(y2, _ERF_A) < (1.0 - 2.0 * u) * _horner(y2, _ERF_B)
    raise ValueError(f"noisy_sign_bits covers z=inf and z=1 only, got {z}")


def stochastic_sign_bits(x, u, sigma, z: int):
    """Sign(x + sigma * F_z^{-1}(u)) >= 0, computed in the compressed domain.

    ``u`` in (0,1) (one word per coordinate, e.g. ``tile_u01``); returns the
    bool wire bit. The inverse-CDF coupling [u > 1 - P_z(x/sigma)] IS the
    sign of the noisy value — the noise itself is never evaluated, which is
    what lets the encode kernels ship 1 bit/coord without an fp32 noise
    surface. ``sigma`` may be a traced scalar; sigma == 0 (static or
    runtime) degrades exactly to the noise-free Sign(x) >= 0 convention of
    ``wire.pack_flat``.
    """
    sig = jnp.asarray(sigma, jnp.float32)
    noisy = noisy_sign_bits(x, u, threshold_scale(sig, z), z)
    return jnp.where(sig > 0, noisy, x >= 0)


def eta_z(z: int) -> float:
    """Normalizer eta_z = 2^{1/(2z)} Gamma(1 + 1/(2z)); eta_inf = 1."""
    if z <= Z_INF:
        return 1.0
    return 2.0 ** (1.0 / (2 * z)) * math.gamma(1.0 + 1.0 / (2 * z))


def sample_z_noise(key: jax.Array, shape, z: int, dtype=jnp.float32) -> jax.Array:
    """Draw i.i.d. xi_z with p.d.f. p_z (Definition 1)."""
    if z <= Z_INF:
        return jax.random.uniform(key, shape, dtype=dtype, minval=-1.0, maxval=1.0)
    if z == 1:
        return jax.random.normal(key, shape, dtype=dtype)
    k_mag, k_sign = jax.random.split(key)
    u = jax.random.gamma(k_mag, 1.0 / (2 * z), shape, dtype=jnp.float32) * 2.0
    mag = u ** (1.0 / (2 * z))
    sign = jax.random.rademacher(k_sign, shape, dtype=jnp.int8)
    return (mag * sign).astype(dtype)


def pdf_z(t, z: int):
    """p_z(t), for tests/benchmarks."""
    t = jnp.asarray(t, jnp.float32)
    if z <= Z_INF:
        return jnp.where(jnp.abs(t) <= 1.0, 0.5, 0.0)
    return jnp.exp(-(t ** (2 * z)) / 2.0) / (2.0 * eta_z(z))


@partial(jax.jit, static_argnames=("z",))
def expected_sign(x, sigma, z: int, *, n_mc: int = 0, key=None):
    """eta_z * sigma * E[Sign(x + sigma*xi_z)], the debiased estimator mean.

    Closed form: eta_z*sigma*E[Sign(x+sigma xi)] = sigma * Psi_z(x/sigma)
    where Psi_z(x) = int_0^x exp(-t^{2z}/2) dt (paper Lemma 3 notation).
    Computed by numerical quadrature (finite z) or exactly (z=inf).
    """
    x = jnp.asarray(x, jnp.float32)
    r = x / sigma
    if z <= Z_INF:
        return sigma * jnp.clip(r, -1.0, 1.0)
    # Gauss-Legendre style quadrature of Psi_z on [0, r] via substitution
    # t = r*u, u in [0,1]:   Psi_z(r) = r * int_0^1 exp(-(r*u)^{2z}/2) du.
    n = 256
    u = (jnp.arange(n, dtype=jnp.float32) + 0.5) / n
    integ = jnp.mean(jnp.exp(-((r[..., None] * u) ** (2 * z)) / 2.0), axis=-1)
    return sigma * r * integ
