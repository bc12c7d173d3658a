"""The plain reference against independent statements of what it computes."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import dense, round as rround, threefry


def test_threefry_20_rounds_is_jax_threefry():
    """At 20 rounds the cipher is the one jax.random is built on, so the
    round structure (injections, rotations, parity word) is checked
    against an implementation that shares no code with this one."""
    from jax._src import prng
    key = jnp.array([0x13198A2E, 0x03707344], jnp.uint32)
    count = jnp.arange(64, dtype=jnp.uint32)
    want = prng.threefry_2x32(key, jnp.concatenate([count, count * 7 + 3]))
    y0, y1 = threefry.threefry2x32(key[0], key[1], count, count * 7 + 3,
                                   rounds=20)
    np.testing.assert_array_equal(np.concatenate([y0, y1]), np.asarray(want))


def test_threefry_known_answer_at_zero():
    """Random123's known answer for Threefry-2x32-20 at a zero key and
    counter."""
    z = jnp.zeros((), jnp.uint32)
    y0, y1 = threefry.threefry2x32(z, z, z, z, rounds=20)
    assert (int(y0), int(y1)) == (0x6B200159, 0x99BA4EFE)


def test_uniforms_are_open_and_centred():
    u = threefry.uniforms(jnp.array([1, 2], jnp.uint32),
                          jnp.arange(4 * 8192, dtype=jnp.uint32))
    assert float(u.min()) > 0.0 and float(u.max()) < 1.0
    assert abs(float(u.mean()) - 0.5) < 0.01
    # the four quarters of a tile read the four halves of one counter's
    # words, so no two quarters repeat each other
    t = np.asarray(u[:8192]).reshape(4, 2048)
    assert len({tuple(np.round(r[:8], 7)) for r in t}) == 4


def test_eta_z():
    assert math.isclose(rround.eta_z(1), math.sqrt(math.pi / 2))
    assert rround.eta_z(0) == 1.0


def test_sign_bit_follows_the_noise_law():
    """[u > 1 - Phi(x / sigma)] is the sign of x + sigma * xi, xi ~ N(0, 1):
    its mean over many coordinates is 2 Phi(x / sigma) - 1."""
    n = 1 << 18
    u = threefry.uniforms(jnp.array([5, 9], jnp.uint32),
                          jnp.arange(n, dtype=jnp.uint32))
    for x in (-0.01, 0.0, 0.004):
        bit = u > 1.0 - rround.cdf(jnp.full((n,), x / 0.01), 1)
        want = 2 * float(jax.scipy.special.ndtr(x / 0.01)) - 1
        assert abs(float(jnp.mean(jnp.where(bit, 1.0, -1.0))) - want) < 0.01


def _tiny_cfg():
    return {"num_attention_heads": 4, "num_key_value_heads": 2,
            "hidden_size": 32, "rms_norm_eps": 1e-6,
            "residual_multiplier": 1.0, "attention_bias": True,
            "rope_theta": 10000.0, "attention_multiplier": 0.125,
            "embedding_multiplier": 1.0, "logits_scaling": 1.0}


def _tiny_params(key, layers=2, d=32, f=64, v=50):
    ks = iter(jax.random.split(key, 16))
    n = lambda *s: jax.random.normal(next(ks), s) * 0.2
    return {"attn": {"wq": n(layers, d, d), "wk": n(layers, d, 16),
                     "wv": n(layers, d, 16), "wo": n(layers, d, d),
                     "bq": n(layers, d), "bk": n(layers, 16),
                     "bv": n(layers, 16)},
            "mlp": {"w1": n(layers, d, f), "w3": n(layers, d, f),
                    "w2": n(layers, f, d)},
            "embed": n(v, d), "ln1": 1 + n(layers, d), "ln2": 1 + n(layers, d),
            "lnf": 1 + n(d)}


def test_loss_is_causal():
    """Changing the last token changes no prediction before it: the loss
    over the first positions is the same."""
    cfg, p = _tiny_cfg(), _tiny_params(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 50)
    other = tok.at[:, -1].set((tok[:, -1] + 1) % 50)
    cut = lambda t: dense.loss(cfg, p, t[:, :-1])
    assert float(cut(tok)) == float(cut(other))
    assert float(dense.loss(cfg, p, tok)) != float(dense.loss(cfg, p, other))


def test_gqa_with_one_group_is_multi_head_attention():
    """With as many key/value heads as query heads, grouped-query attention
    is plain multi-head attention: repeating each key/value head over its
    group must give the same loss as widening the projections."""
    cfg = dict(_tiny_cfg(), num_key_value_heads=2)
    p = _tiny_params(jax.random.PRNGKey(2))
    tok = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, 50)
    wide = jax.tree.map(lambda x: x, p)
    for w in ("wk", "wv"):
        x = p["attn"][w].reshape(2, 32, 2, 8)
        wide["attn"][w] = jnp.repeat(x, 2, axis=2).reshape(2, 32, 32)
    for b in ("bk", "bv"):
        x = p["attn"][b].reshape(2, 2, 8)
        wide["attn"][b] = jnp.repeat(x, 2, axis=1).reshape(2, 32)
    got = dense.loss(cfg, p, tok)
    want = dense.loss(dict(cfg, num_key_value_heads=4), wide, tok)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


@pytest.mark.parametrize("x", [0.0, 1e-3, 0.37, 448.0 * 3])
def test_fp8_control_rounds_to_three_mantissa_bits(x):
    y = jnp.array([x, -x / 3, x / 1000 + 1e-9], jnp.float32)
    q = rround.fp8(y)
    amax = float(jnp.max(jnp.abs(y)))
    # the largest entry maps to 448 exactly, the rest within e4m3's step
    np.testing.assert_allclose(np.asarray(q), np.asarray(y),
                               rtol=2 ** -3, atol=amax * 2 ** -9 + 1e-30)
    g = jax.grad(lambda v: jnp.sum(rround.fp8(v) * 2.0))(y)
    np.testing.assert_array_equal(np.asarray(g), 2.0)
