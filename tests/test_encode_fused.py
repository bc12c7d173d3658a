"""Fused client encode: counter-based in-kernel noise equivalence suite.

The client encode has four implementations that must agree:

  "reference"  dense jax.random draw + pack (the statistical oracle)
  "jnp"        fused counter-based single pass (default CPU path)
  "jnp" + encode_chunk_tiles > 0   chunked-scan variant (bounded jaxpr-level
               noise window)
  "pallas"     in-kernel counter noise (TPU; interpret mode on CPU)

Contract (see core/noise.py and compression.py docstrings):
  * the three fused paths are BIT-EXACT against each other for the same
    client key — same global element counters, same per-tile word layout,
    same f32 threshold math;
  * the fused bit [u > 1 - P_z(x/sigma)] is the inverse-CDF coupling of
    Sign(x + sigma * F_z^{-1}(u)) — identically distributed to the reference
    draw (checked against the closed-form expected sign and pdf_z);
  * no (n_clients, d) fp32 noise buffer exists: jaxpr-level for the chunked
    and pallas paths, compiled-buffer-level for the single-pass default.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.core import fedavg
from repro.core import noise as Z
from repro.core import wire
from repro.core.context import RoundContext
from repro.kernels.zsign import ops
from repro.kernels.zsign import zsign as ZK

TILE = C.ENCODE_TILE


def test_encode_tile_matches_kernel():
    """compression.ENCODE_TILE mirrors the kernel tile — keep in sync."""
    assert C.ENCODE_TILE == ops.TILE


def test_threefry_matches_random123_vectors():
    """The cipher structure is canonical Threefry-2x32: at 20 rounds it must
    reproduce the published Random123 known-answer vectors exactly, and the
    13-round production stream is pinned against silent drift."""
    orig = Z.THREEFRY_ROUNDS
    try:
        Z.THREEFRY_ROUNDS = 20
        for (c0, c1), (k0, k1), want in [
                ((0, 0), (0, 0), (0x6B200159, 0x99BA4EFE)),
                ((0xFFFFFFFF, 0xFFFFFFFF), (0xFFFFFFFF, 0xFFFFFFFF),
                 (0x1CB996FC, 0xBB002BE7)),
                ((0x243F6A88, 0x85A308D3), (0x13198A2E, 0x03707344),
                 (0xC4923A9C, 0x483DF7A0))]:
            y0, y1 = Z.threefry2x32(jnp.uint32(k0), jnp.uint32(k1),
                                    jnp.uint32(c0), jnp.uint32(c1))
            assert (int(y0), int(y1)) == want
    finally:
        Z.THREEFRY_ROUNDS = orig
    assert Z.THREEFRY_ROUNDS == 13  # the cited BigCrush-minimal variant
    y0, y1 = Z.threefry2x32(jnp.uint32(0), jnp.uint32(0),
                            jnp.uint32(0), jnp.uint32(0))
    # regression pin of the production 13-round stream (matches the
    # Random123 R=13 unrolling: no injection after the partial last group)
    assert (int(y0), int(y1)) == (0x9D1C5EC6, 0x8BD50731)


# ---------------------------------------------------------------------------
# bit-exactness across fused backends
# ---------------------------------------------------------------------------

#: widths: one tile's part, whole tiles, off the lane (padded to the next
#: 128), whole lanes but neither whole tiles nor whole blocks (no pad: the
#: kernel reads past the buffer and encodes zeros there), and longer than
#: one kernel block, whose last block is ragged
WIDE = 65 * 8192 + 128 * 5


@pytest.mark.parametrize("z", [1, Z.Z_INF])
@pytest.mark.parametrize("d", [64, 8192, 3 * 8192 + 17, 100_003,
                               5 * 8192 + 128 * 9, WIDE])
@pytest.mark.parametrize("sigma", [0.3, 5.0, 0.0, None])
def test_fused_backends_bit_exact(z, d, sigma):
    """A sigma of 0.0 is a runtime 0 (the noise-free sign); None is
    ``add_noise=False``, which draws no randomness at all."""
    key = jax.random.PRNGKey(d + z)
    flat = jax.random.normal(jax.random.PRNGKey(0), (d,))
    add = sigma is not None
    sig = sigma if add else 0.0
    assert d < WIDE or ZK.encode_tiles(-(-d // TILE)) < -(-d // TILE)

    def jnp_enc(chunk):
        return C.fused_sign_encode_jnp(flat, key, sig, z=z, add_noise=add,
                                       chunk_tiles=chunk)
    got = {
        "jnp": jnp_enc(0),
        "jnp_chunk1": jnp_enc(1),
        "jnp_chunk3": jnp_enc(3),
        "pallas": ops.zsign_encode_fused(flat, key, sig, z=z, add_noise=add),
    }
    n_bytes = -(-d // TILE) * TILE // 8
    for name, p in got.items():
        assert p.shape == (n_bytes,) and p.dtype == jnp.uint8, name
        np.testing.assert_array_equal(np.asarray(got["jnp"]), np.asarray(p),
                                      err_msg=name)
    if not add or sigma == 0.0:
        np.testing.assert_array_equal(
            np.asarray(got["jnp"]),
            np.asarray(wire.pack_flat(jnp.pad(flat, (0, 8 * n_bytes - d)))))


@pytest.mark.parametrize("n_tiles,want", [
    (1, 1), (13, 13), (64, 64), (65, 64), (60307, 64), (73220, 64)])
def test_encode_tiles_per_step(n_tiles, want):
    """Tiles per encode grid step: a power of two whose double-buffered
    blocks fit the VMEM budget, or the whole buffer when it is shorter."""
    t = ZK.encode_tiles(n_tiles)
    assert t == want
    assert 2 * t * (4 * TILE + TILE // 8) <= ZK.ENCODE_VMEM
    assert t == n_tiles or t & (t - 1) == 0


@pytest.mark.parametrize("name", ["zsign", "zsign_packed", "stosign"])
def test_compressor_backends_bit_exact(name):
    """Through the compressor API (incl. stosign's dynamic sigma = ||flat||),
    jnp and pallas encode backends ship identical wire bytes."""
    d = 2 * 8192 + 117
    flat = jax.random.normal(jax.random.PRNGKey(1), (d,))
    key = jax.random.PRNGKey(7)
    opts = "" if name == "stosign" else "z=1,sigma=0.4,"
    outs = {}
    for backend in ["jnp", "pallas"]:
        comp = C.Pipeline(f"{name}({opts}encode_backend={backend})")
        outs[backend], _ = comp.encode(key, flat, None)
    np.testing.assert_array_equal(np.asarray(outs["jnp"]),
                                  np.asarray(outs["pallas"]))


def test_vmapped_encode_matches_per_client():
    """Under the engine's client vmap each client gets its own counter
    stream; rows match per-client single calls exactly."""
    n, d = 5, 8192 + 13
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    flats = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    comp = C.Pipeline("zsign(z=1,sigma=0.5,encode_backend=jnp)")
    stacked = jax.vmap(lambda k, f: comp.encode(k, f, None)[0])(keys, flats)
    for i in range(n):
        single, _ = comp.encode(keys[i], flats[i], None)
        np.testing.assert_array_equal(np.asarray(stacked[i]),
                                      np.asarray(single))
    # distinct clients -> distinct streams
    assert np.any(np.asarray(stacked[0]) != np.asarray(stacked[1]))


@pytest.mark.parametrize("route", ["vmap", "grid"])
@pytest.mark.parametrize("n,d", [(5, 1024), (3, 2 * TILE + 77),
                                 (3, 5 * TILE + 128 * 9)])
def test_vmapped_pallas_encode_matches_per_client(route, n, d):
    """A client stack reproduces each client's unbatched byte stream
    bit-exactly: through the pallas backend's custom vmap rule ("vmap": in
    interpret mode the tile-scanned jnp twin), and through the kernel with
    the client axis in its grid ("grid", the rule's TPU path), here in
    blocks of 2 tiles so that each client's last block is ragged."""
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    flats = jax.random.normal(jax.random.PRNGKey(4), (n, d))
    comp = C.Pipeline("zsign(z=1,sigma=0.5,encode_backend=pallas)")
    if route == "vmap":
        stacked = jax.jit(jax.vmap(
            lambda k, f: comp.encode(k, f, None)[0]))(keys, flats)
    else:
        x = jnp.pad(flats, ((0, 0), (0, (-d) % 128))).reshape(n, -1, 128)
        key2 = jnp.stack(Z.key_words(keys), axis=-1)
        stacked = ZK.compress_rng_pallas(
            x, key2, jnp.full((n,), 0.5), z=1, interpret=True,
            tiles=2).reshape(n, -1)
    for i in range(n):
        single, _ = comp.encode(keys[i], flats[i], None)
        np.testing.assert_array_equal(np.asarray(stacked[i]),
                                      np.asarray(single), err_msg=str(d))


def test_vmapped_pallas_encode_cost_linear_in_clients():
    """Scaling regression (the historical vmap blowup): JAX's default
    pallas batching rule made each interpret-mode grid step rewrite the
    whole batched output, so per-client encode cost grew ~linearly with
    the vmap width (measured 50 -> 730 us/client from n=16 to n=128 at
    d=1024 — ~14x). The custom vmap rule is elementwise-linear: pin the
    per-client cost ratio n=128 / n=16 to a small factor (generous bound;
    the regression is an order of magnitude)."""
    import time

    d = 1024
    comp = C.Pipeline("zsign_packed(z=1,sigma=0.5)")

    def per_client_seconds(n):
        keys = jax.random.split(jax.random.PRNGKey(1), n)
        flats = jax.random.normal(jax.random.PRNGKey(2), (n, d))
        f = jax.jit(jax.vmap(lambda f_, k: comp.encode(k, f_, None)[0]))
        jax.block_until_ready(f(flats, keys))      # compile
        best = min(
            _timed(lambda: jax.block_until_ready(f(flats, keys)), time)
            for _ in range(5))
        return best / n

    assert per_client_seconds(128) < 4.0 * per_client_seconds(16)


def _timed(fn, time):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def test_unknown_encode_backend_raises():
    comp = C.Pipeline("zsign(encode_backend=nope)")
    with pytest.raises(ValueError, match="unknown encode backend"):
        comp.encode(jax.random.PRNGKey(0), jnp.ones((8,)), None)


# ---------------------------------------------------------------------------
# distribution: counter noise vs pdf_z / closed-form expected sign
# ---------------------------------------------------------------------------

def test_counter_noise_z1_is_standard_normal():
    xi = np.asarray(Z.counter_noise(jax.random.PRNGKey(11), 400_000, 1),
                    np.float64)
    assert abs(xi.mean()) < 0.01
    assert abs(xi.std() - 1.0) < 0.01
    assert abs((xi ** 3).mean()) < 0.03          # symmetry
    assert abs((xi ** 4).mean() - 3.0) < 0.1     # gaussian kurtosis
    # KS distance vs the exact CDF
    s = np.sort(xi)
    cdf = 0.5 * (1.0 + np.array([math.erf(v / math.sqrt(2)) for v in
                                 s[:: len(s) // 2000]]))
    emp = np.arange(len(s))[:: len(s) // 2000] / len(s)
    assert np.max(np.abs(cdf - emp)) < 0.01


def test_counter_noise_zinf_is_uniform():
    xi = np.asarray(Z.counter_noise(jax.random.PRNGKey(12), 400_000, Z.Z_INF),
                    np.float64)
    assert xi.min() > -1.0 and xi.max() < 1.0
    assert abs(xi.mean()) < 0.01
    assert abs(xi.std() - 1.0 / math.sqrt(3)) < 0.005
    # KS vs the linear CDF
    s = np.sort(xi)
    emp = np.arange(len(s))[:: len(s) // 2000] / len(s)
    assert np.max(np.abs((s[:: len(s) // 2000] + 1) / 2 - emp)) < 0.01


@pytest.mark.parametrize("z", [1, Z.Z_INF])
def test_counter_noise_matches_pdf_z_histogram(z):
    """Histogram of the counter stream vs Definition 1's density."""
    xi = np.asarray(Z.counter_noise(jax.random.PRNGKey(13), 400_000, z))
    edges = np.linspace(-2.5, 2.5, 26)
    hist, _ = np.histogram(xi, bins=edges, density=True)
    centers = 0.5 * (edges[:-1] + edges[1:])
    want = np.asarray(Z.pdf_z(centers, z))
    # uniform's discontinuity at +-1 lands inside a bin; skip those two
    keep = np.abs(np.abs(centers) - 1.0) > 0.15 if z <= Z.Z_INF else \
        np.ones_like(centers, bool)
    np.testing.assert_allclose(hist[keep], want[keep], atol=0.02)


@pytest.mark.parametrize("z", [1, Z.Z_INF])
def test_fused_mean_sign_matches_expected_sign(z):
    """eta_z * sigma * mean(decoded signs) ~= expected_sign (Lemma 3's
    closed form) — the fused Bernoulli bit has the exact sign law of the
    additive-noise encoder."""
    sigma = 1.3
    grid = jnp.linspace(-2.0, 2.0, 32)
    reps = 8192
    flat = jnp.repeat(grid, reps)                # 32 * 8192 coords
    payload = C.fused_sign_encode_jnp(flat, jax.random.PRNGKey(5), sigma, z=z)
    signs = np.asarray(wire.unpack_signs(payload), np.float64)[: flat.size]
    mean_sign = signs.reshape(32, reps).mean(axis=1)
    got = Z.eta_z(z) * sigma * mean_sign
    want = np.asarray(Z.expected_sign(grid, sigma, z))
    np.testing.assert_allclose(got, want, atol=0.05)


@pytest.mark.parametrize("z", [1, Z.Z_INF])
def test_threshold_is_inverse_cdf_coupling(z):
    """The fused bit [u > 1 - P_z(x/s)] equals Sign(x + s * F_z^{-1}(u))
    computed from the SAME counter stream, up to f32 boundary rounding."""
    d = 100_000
    key = jax.random.PRNGKey(21)
    x = jax.random.normal(jax.random.PRNGKey(22), (d,))
    sigma = 0.7
    payload = C.fused_sign_encode_jnp(x, key, sigma, z=z)
    got = np.asarray(wire.unpack_signs(payload))[:d] > 0
    xi = Z.counter_noise(key, d, z)
    want = np.asarray(x + sigma * xi >= 0)
    assert (got == want).mean() > 0.9999


def test_stosign_fused_mean_sign_matches_clip():
    """stosign = z=inf with sigma = ||flat||: mean sign of many independent
    encodings approaches clip(x / ||x||, -1, 1) (exactly unbiased regime)."""
    reps, vals = 4096, jnp.asarray([-0.5, -0.1, 0.0, 0.2, 0.6])
    flat = jnp.repeat(vals, reps)
    comp = C.Pipeline("stosign(encode_backend=jnp)")
    payload, _ = comp.encode(jax.random.PRNGKey(9), flat, None)
    signs = np.asarray(wire.unpack_signs(payload), np.float64)[: flat.size]
    mean_sign = signs.reshape(5, reps).mean(axis=1)
    nrm = float(jnp.linalg.norm(flat))
    want = np.clip(np.asarray(vals) / nrm, -1.0, 1.0)
    np.testing.assert_allclose(mean_sign, want, atol=0.03)


# ---------------------------------------------------------------------------
# reference backend and fallbacks
# ---------------------------------------------------------------------------

def test_reference_backend_is_dense_draw():
    """encode_backend="reference" pins the pre-fused semantics exactly:
    pack_flat(flat + sigma * sample_z_noise(key))."""
    d, z, sigma = 1000, 1, 0.6
    key = jax.random.PRNGKey(2)
    flat = jax.random.normal(jax.random.PRNGKey(1), (d,))
    comp = C.Pipeline(f"zsign(z={z},sigma={sigma},"
                      f"encode_backend=reference)")
    got, _ = comp.encode(key, flat, None)
    want = wire.pack_flat(flat + sigma * Z.sample_z_noise(key, (d,), z))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_finite_z_falls_back_to_dense():
    """z = 2 has no counter transform: every backend routes to the dense
    draw and produces the reference bytes for the same key."""
    d = 500
    key = jax.random.PRNGKey(4)
    flat = jax.random.normal(jax.random.PRNGKey(3), (d,))
    ref, _ = C.Pipeline("zsign(z=2,sigma=0.5,"
                        "encode_backend=reference)").encode(key, flat, None)
    for backend in ["auto", "jnp"]:
        got, _ = C.Pipeline(f"zsign(z=2,sigma=0.5,"
                            f"encode_backend={backend})").encode(
                                key, flat, None)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("name", ["zsign", "zsign_packed"])
@pytest.mark.parametrize("backend", ["jnp", "pallas", "reference"])
def test_sigma_zero_is_noise_free_on_all_backends(name, backend):
    """Vanilla-SignSGD mode (sigma == 0): every backend produces the exact
    noise-free signs."""
    d = 8192 + 5
    flat = jax.random.normal(jax.random.PRNGKey(6), (d,))
    comp = C.Pipeline(f"{name}(z=1,sigma=0.0,encode_backend={backend})")
    payload, _ = comp.encode(jax.random.PRNGKey(0), flat, None)
    signs = np.asarray(wire.unpack_signs(payload))[:d]
    want = np.where(np.asarray(flat) >= 0, 1, -1)
    np.testing.assert_array_equal(signs, want)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _walk_eqns(inner)
            if isinstance(v, (list, tuple)):
                for vv in v:
                    inner = getattr(vv, "jaxpr", None)
                    if inner is not None:
                        yield from _walk_eqns(inner)


def test_sigma_zero_packed_draws_no_rng():
    """Regression (satellite): PackedZSign's dense path used to draw (and
    discard) a full noise buffer when sigma == 0 — no PRNG primitive may
    appear in any sigma == 0 encode jaxpr."""
    d = 8192
    flat = jnp.ones((d,))
    for backend in ["reference", "jnp", "pallas"]:
        comp = C.Pipeline(f"zsign_packed(z=1,sigma=0.0,"
                          f"encode_backend={backend})")
        jaxpr = jax.make_jaxpr(
            lambda k, f: comp.encode(k, f, None)[0])(
                jax.random.PRNGKey(0), flat)
        for eqn in _walk_eqns(jaxpr.jaxpr):
            assert "threefry" not in eqn.primitive.name, (backend, eqn)
            assert "erf" not in eqn.primitive.name, (backend, eqn)


# ---------------------------------------------------------------------------
# no (n_clients, d) fp32 noise buffer
# ---------------------------------------------------------------------------

# structural data movement of the input buffer itself (padding x to the
# tile boundary, reshapes) is not noise — only COMPUTED f32 values count.
_STRUCTURAL = {"pad", "reshape", "squeeze", "transpose", "broadcast_in_dim",
               "convert_element_type", "slice", "dynamic_slice",
               "dynamic_update_slice", "concatenate", "copy",
               # transparent containers: their bodies are walked instead
               "jit", "closed_call", "custom_jvp_call", "custom_vjp_call"}


def _max_f32_outvar_bytes(jaxpr):
    worst = 0
    for eqn in _walk_eqns(jaxpr):
        if eqn.primitive.name in _STRUCTURAL:
            continue
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            if aval.dtype == jnp.float32:
                n = 1
                for s in aval.shape:
                    n *= int(s)
                worst = max(worst, 4 * n)
    return worst


@pytest.mark.parametrize("setup", [
    ("pallas", 0), ("jnp", 2),
])
def test_no_dense_noise_buffer_in_encode_jaxpr(setup):
    """Jaxpr scan: the chunked-jnp and pallas fused encodes never produce an
    fp32 intermediate anywhere near (n_clients, d) — the largest fp32 outvar
    in the whole client fan-out stays bounded by the chunk window. The
    reference dense draw (sanity check) produces the full stacked buffer."""
    backend, chunk = setup
    n, d = 16, 8 * TILE + 100
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    flats = jnp.zeros((n, d))
    comp = C.Pipeline(f"zsign(z=1,sigma=0.5,encode_backend={backend},"
                      f"encode_chunk_tiles={chunk})")
    fan_out = jax.vmap(lambda k, f: comp.encode(k, f, None)[0])
    worst = _max_f32_outvar_bytes(jax.make_jaxpr(fan_out)(keys, flats).jaxpr)
    stacked_noise_bytes = 4 * n * d
    limit = 4 * n * max(chunk, 1) * TILE  # the chunk window (pallas: 0 eqns)
    assert worst < stacked_noise_bytes / 4, (backend, worst)
    assert worst <= limit, (backend, worst)

    ref = C.Pipeline("zsign(z=1,sigma=0.5,encode_backend=reference)")
    worst_ref = _max_f32_outvar_bytes(
        jax.make_jaxpr(jax.vmap(lambda k, f: ref.encode(k, f, None)[0]))(
            keys, flats).jaxpr)
    assert worst_ref >= stacked_noise_bytes  # the pathology, still visible


def test_no_dense_noise_buffer_in_compiled_single_pass():
    """Compiled-buffer scan for the single-pass jnp default: XLA fuses the
    whole counter->threshold->bitpack chain into the uint8 payload, so the
    compiled round allocates ~zero temp where the reference dense draw
    allocates the full (n_clients, d) fp32 noise surface (and more)."""
    n, d = 8, 16 * TILE + 1
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    flats = jnp.zeros((n, d))
    temps = {}
    for backend in ["jnp", "reference"]:
        comp = C.Pipeline(f"zsign(z=1,sigma=0.5,"
                          f"encode_backend={backend})")
        fan_out = jax.jit(jax.vmap(lambda k, f: comp.encode(k, f, None)[0]))
        mem = fan_out.lower(keys, flats).compile().memory_analysis()
        temps[backend] = mem.temp_size_in_bytes
    stacked_noise_bytes = 4 * n * d
    assert temps["jnp"] < stacked_noise_bytes / 4, temps
    assert temps["reference"] >= stacked_noise_bytes, temps


# ---------------------------------------------------------------------------
# client groups run as shards of the round's shard executor
# ---------------------------------------------------------------------------

def _consensus(comp, groups, n, d, seed=0):
    y = jax.random.normal(jax.random.PRNGKey(seed), (1, groups * n, 1, d))
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    cfg = fedavg.FedConfig(n_clients=n, client_groups=groups,
                           client_lr=0.01, server_lr=0.3)
    step = jax.jit(fedavg.build_round_step(loss_fn, comp, cfg))
    st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                  jax.random.PRNGKey(1))
    return step, st, y.reshape(groups, n, 1, d)


@pytest.mark.parametrize("mask_on", [True, False])
def test_group_scan_bit_identical_to_vmap_path(mask_on):
    """8 clients as 2x4 (two shards of 4) vs 1x8 (one shard of 8): the
    SignFoldAcc carry folds the two shards in the single 8-client
    sign-reduce's order, so params must be BIT-identical, including under
    partial participation."""
    d = 80
    outs = {}
    for groups, n in [(1, 8), (2, 4)]:
        comp = C.Pipeline("zsign(z=1,sigma=1.0)")
        step, st, y = _consensus(comp, groups, n, d, seed=5)
        mask = jnp.ones((groups, n))
        if mask_on:
            mask = mask.reshape(1, 8).at[0, 2].set(0.0).at[0, 7].set(
                0.0).reshape(groups, n)
        st = st._replace(rng=jax.random.PRNGKey(42))
        for _ in range(5):
            st, m = step(st, {"y": y}, mask)
        outs[groups] = np.asarray(st.params["x"])
    np.testing.assert_array_equal(outs[1], outs[2])


def test_group_stack_aggregate_equals_per_group_sum():
    """One sign_reduce over the (G*N, n_bytes) stack == per-group reduces
    summed: exact for 0/1 masks, f32-rounding-close for EF scale weights."""
    G, N, n_bytes = 3, 8, 1024
    rng = np.random.RandomState(0)
    packed = jnp.asarray(rng.randint(0, 256, (G, N, n_bytes)), jnp.uint8)
    mask = jnp.asarray(rng.randint(0, 2, (G, N)).astype(np.float32))
    one = C.sign_reduce(packed.reshape(G * N, n_bytes), mask.reshape(-1),
                        "jnp")
    per = sum(C.sign_reduce(packed[g], mask[g], "jnp") for g in range(G))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(per))
    scales = jnp.asarray(rng.rand(G, N).astype(np.float32))
    one_w = C.sign_reduce(packed.reshape(G * N, n_bytes),
                          (mask * scales).reshape(-1), "jnp")
    per_w = sum(C.sign_reduce(packed[g], mask[g] * scales[g], "jnp")
                for g in range(G))
    np.testing.assert_allclose(np.asarray(one_w), np.asarray(per_w),
                               rtol=1e-5, atol=1e-5)


def test_group_scan_emits_payload_stack_not_dense_partials():
    """Jaxpr of the G>1 round for a sign compressor: the G groups run as G
    shards of N through the shard scan, whose carry is the O(d) wire
    accumulator; no (G*N, d) f32 array of gradients or decoded partials
    appears anywhere in the round."""
    d = 2 * TILE
    comp = C.Pipeline("zsign(z=1,sigma=0.5,encode_chunk_tiles=1)")
    G, n = 4, 4
    cfg = fedavg.FedConfig(n_clients=n, client_groups=G, client_lr=0.01,
                           server_lr=0.3)
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    step = fedavg.build_round_step(loss_fn, comp, cfg)
    st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                  jax.random.PRNGKey(1))
    # scalar per-client targets: any (G*N)*d-sized array in the jaxpr is a
    # genuine full-cohort buffer, never input data
    batch = {"y": jnp.zeros((G, n, 1, 1))}
    jaxpr = jax.make_jaxpr(step)(st, batch, jnp.ones((G, n)))
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    group_scans = [e for e in eqns if e.primitive.name == "scan"
                   and e.params["length"] == G]
    assert group_scans, "the G groups must lower to one shard scan"
    for scan in group_scans:
        for v in scan.outvars[:scan.params["num_carry"]]:
            assert int(np.prod(v.aval.shape)) <= d, \
                f"group scan carries more than the O(d) accumulator: {v.aval}"
    for eqn in eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if getattr(aval, "dtype", None) == jnp.float32:
                assert int(np.prod(aval.shape)) < G * n * d, \
                    f"full-cohort f32 buffer in the G>1 round: {eqn}"


# ---------------------------------------------------------------------------
# static participation-mask dispatch
# ---------------------------------------------------------------------------

def test_weights_are_mask_dispatches_popcount():
    """build_round_step(weights_are_mask=True) routes the jnp sign-reduce
    through wire.unpack_sum_mask (population_count in the jaxpr); the
    default keeps the LUT path."""
    n, n_bytes = 8, 256
    payload = jnp.zeros((n, n_bytes), jnp.uint8)
    mask = jnp.ones((n,))
    for flag, want in [(True, True), (False, False)]:
        comp = C.Pipeline(f"zsign(agg_backend=jnp,"
                          f"weights_are_mask={flag})")
        jaxpr = jax.make_jaxpr(
            lambda p, m: comp.aggregate(p, m, 8 * n_bytes))(payload, mask)
        has_pc = any(e.primitive.name == "population_count"
                     for e in _walk_eqns(jaxpr.jaxpr))
        assert has_pc == want, (flag, has_pc)


def test_weights_are_mask_identical_results():
    """The popcount specialization is bit-identical for real 0/1 masks,
    end-to-end through the engine."""
    d = 120
    outs = {}
    for flag in [False, True]:
        comp = C.Pipeline("zsign(z=1,sigma=1.0)")
        loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
        cfg = fedavg.FedConfig(n_clients=6, client_lr=0.01, server_lr=0.3)
        step = jax.jit(fedavg.build_round_step(
            loss_fn, comp, cfg, RoundContext(weights_are_mask=flag)))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        y = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 1, d))
        mask = jnp.ones((1, 6)).at[0, 3].set(0.0)
        for _ in range(4):
            st, _ = step(st, {"y": y}, mask)
        outs[flag] = np.asarray(st.params["x"])
    np.testing.assert_array_equal(outs[False], outs[True])


def test_efsign_has_no_mask_flag():
    """EF weights are mask * scale — never a pure membership mask; the
    engine must not be able to flip a flag on it."""
    assert "weights_are_mask" not in {
        f.name for f in __import__("dataclasses").fields(
            C.Pipeline("ef|zsign"))}
