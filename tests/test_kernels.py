"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

On the CPU the kernels run in interpret mode, which checks their logic but
not what the TPU compiler accepts: tests/test_tpu_compile.py compiles them
for a v5e chip, and chip_smoke.py compares them with the jnp backend on
one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo_compat import given, settings, st

from repro.kernels.zsign import ops, ref


@pytest.mark.parametrize("size", [8, 64, 8192, 8192 * 2, 8192 * 3 + 17,
                                  100_003, 262_144])
@pytest.mark.parametrize("sigma", [0.0, 0.3, 5.0])
def test_compress_matches_oracle(size, sigma):
    k1, k2 = jax.random.split(jax.random.PRNGKey(size))
    x = jax.random.normal(k1, (size,))
    noise = jax.random.normal(k2, (size,))
    got = ops.zsign_compress(x, noise, sigma)
    pad = (-size) % ops.TILE
    want = ref.zsign_compress_ref(jnp.pad(x, (0, pad)), jnp.pad(noise, (0, pad)),
                                  sigma)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_clients", [1, 2, 16])
@pytest.mark.parametrize("size", [8192, 24_576, 99_991])
def test_decompress_sum_matches_oracle(n_clients, size):
    keys = jax.random.split(jax.random.PRNGKey(7), n_clients * 2)
    packed = []
    for i in range(n_clients):
        x = jax.random.normal(keys[2 * i], (size,))
        nz = jax.random.normal(keys[2 * i + 1], (size,))
        packed.append(ops.zsign_compress(x, nz, 1.0))
    packed = jnp.stack(packed)
    got = ops.zsign_decompress_sum(packed, size)
    want = ref.zsign_decompress_sum_ref(packed)[:size]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_compress_decompress_end_to_end_sign_mean():
    """kernel pipeline == direct sign computation (the int8 psum path)."""
    n, size = 8, 16_384
    xs = jax.random.normal(jax.random.PRNGKey(0), (n, size))
    ns = jax.random.normal(jax.random.PRNGKey(1), (n, size))
    sigma = 0.7
    packed = jnp.stack([ops.zsign_compress(xs[i], ns[i], sigma)
                        for i in range(n)])
    mean_sign = ops.zsign_decompress_sum(packed, size) / n
    direct = jnp.mean(jnp.where(xs + sigma * ns >= 0, 1.0, -1.0), axis=0)
    np.testing.assert_allclose(np.asarray(mean_sign), np.asarray(direct))


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=70_000),
       st.floats(min_value=0.0, max_value=10.0, allow_nan=False))
def test_compress_property_any_shape(size, sigma):
    x = jnp.asarray(np.random.RandomState(size).randn(size), jnp.float32)
    noise = jnp.asarray(np.random.RandomState(size + 1).randn(size), jnp.float32)
    got = ops.zsign_compress(x, noise, sigma)
    # unpack and compare against elementwise signs
    from repro.core.compression import unpack_signs
    signs = unpack_signs(got)[:size]
    want = jnp.where(x + sigma * noise >= 0, 1, -1).astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(signs), np.asarray(want))


def test_wire_size_is_one_bit_per_coord():
    x = jnp.ones(8192)
    out = ops.zsign_compress(x, x, 0.0)
    assert out.size == 8192 // 8 and out.dtype == jnp.uint8


def test_packed_compressor_matches_int8_path():
    """PackedZSignCompressor (Pallas 1-bit wire) produces the same training
    trajectory as the dense int8 z-sign path (same rng stream)."""
    import numpy as np
    from repro.core import compression, fedavg
    d, n = 100, 4
    y = jax.random.normal(jax.random.PRNGKey(0), (1, n, d))
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    cfg = fedavg.FedConfig(n_clients=n, client_lr=0.01, server_lr=0.05)
    batch = {"y": y[:, :, None]}
    mask = jnp.ones((1, n))
    outs = {}
    for name in ["zsign", "zsign_packed"]:
        comp = compression.Pipeline(f"{name}(z=1,sigma=1.0)")
        step = jax.jit(fedavg.build_round_step(loss_fn, comp, cfg))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        for _ in range(20):
            st, m = step(st, batch, mask)
        outs[name] = np.asarray(st.params["x"])
        assert float(m.uplink_bits) == n * d  # 1 bit per coordinate
    np.testing.assert_allclose(outs["zsign"], outs["zsign_packed"], atol=1e-8)


@pytest.mark.parametrize("size", [64, 8192, 50_000])
@pytest.mark.parametrize("scale", [0.1, 1.0])
def test_ef_kernel_matches_oracle(size, scale):
    from repro.kernels.efsign import ops as E
    from repro.kernels.efsign import ref as ER
    k1, k2 = jax.random.split(jax.random.PRNGKey(size))
    g = jax.random.normal(k1, (size,))
    e = jax.random.normal(k2, (size,)) * 0.3
    q, e_new = E.ef_sign_update(g, e, scale)
    q_ref, e_ref = ER.ef_sign_update_ref(g, e, scale)
    np.testing.assert_allclose(np.asarray(q), np.asarray(q_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(e_new), np.asarray(e_ref), atol=1e-6)
    # EF invariant: q + e_new == g + e exactly (compression error conserved)
    np.testing.assert_allclose(np.asarray(q + e_new), np.asarray(g + e),
                               atol=1e-5)


def test_efsign_compressor_kernel_path_matches():
    """Pure-jnp and fused-Pallas EF paths produce identical wire payloads
    and residual buffers over repeated flat encodes."""
    from repro.core import compression
    import numpy as np
    flat = jnp.asarray(np.random.RandomState(0).randn(500), jnp.float32)
    c1 = compression.Pipeline("ef|zsign")
    c2 = compression.Pipeline("ef|zsign(use_kernel=true)")
    s1, s2 = c1.init_state(500), c2.init_state(500)
    for i in range(5):
        e1, s1 = c1.encode(None, flat, s1)
        e2, s2 = c2.encode(None, flat, s2)
    # kernel payload is tile-padded; shared byte prefix must be identical
    n_bytes = e1["packed"].size
    np.testing.assert_array_equal(np.asarray(e1["packed"]),
                                  np.asarray(e2["packed"])[:n_bytes])
    np.testing.assert_allclose(np.asarray(e1["scale"]),
                               np.asarray(e2["scale"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1["ef"]), np.asarray(s2["ef"]),
                               atol=1e-5)


def test_packed_wire_bytes_match_pure_jnp_pack():
    """Kernel bitpack and wire.pack_flat produce the same byte stream on the
    shared coordinate range (kernel pads to its 8192 tile)."""
    from repro.core import wire
    d = 10_003
    y = jax.random.normal(jax.random.PRNGKey(3), (d,))
    got = ops.zsign_compress(y, jnp.zeros((d,)), 0.0)
    want = wire.pack_flat(y)
    np.testing.assert_array_equal(np.asarray(got)[: want.size],
                                  np.asarray(want))
