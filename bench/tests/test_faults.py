"""The comparison fails a run whose timed path is broken underneath, and the
lower-precision control put in the program's place, at a tiny size on the
CPU. (The exchange between chips is in test_exchange.py.)"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

HOOKS = dict(skip_device_check=True,
             peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def unchanged(step):
    """A step that returns its state unchanged."""
    def broken(state, batch, mask):
        copy = jax.tree.map(jnp.copy, state)
        return state, step(copy, batch, mask)[1]
    return broken


def half_batch(step):
    """Half of the clients left out, the mean taken over the rest."""
    def broken(state, batch, mask):
        n = mask.shape[-1]
        keep = (jnp.arange(n) < n // 2).astype(mask.dtype)
        return step(state, batch, mask * keep)
    return broken


def wrong_keys(step):
    """The clients encode under keys drawn from a key that is not the
    server's."""
    def broken(state, batch, mask):
        rng = jax.random.fold_in(state.rng, 1)
        return step(state._replace(rng=rng), batch, mask)
    return broken


def flipped(step):
    """The server's update applied with the wrong sign."""
    def broken(state, batch, mask):
        old = jax.tree.map(jnp.copy, state.params)
        new, m = step(state, batch, mask)
        params = jax.tree.map(
            lambda o, n: (2 * o.astype(jnp.float32)
                          - n.astype(jnp.float32)).astype(n.dtype),
            old, new.params)
        return new._replace(params=params), m
    return broken


@pytest.mark.parametrize("fault", [unchanged, half_batch, wrong_keys,
                                   flipped])
def test_broken_step_is_not_correct(tmp_path, fault):
    root = tiny.make_root(tmp_path)
    r = harness.run(root, "tiny.mix", 2 ** 31 + 3, 0.2, False,
                    time.perf_counter(), harness.Hooks(**HOOKS,
                                                       wrap_step=fault))
    assert not r["correct"]
    over = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert over, r["checks"]


@pytest.mark.parametrize("stand_in", [
    dict(precision="fp8"),      # the control: fp8 contractions, bf16 weights
    dict(fault="keys"),         # clients encoded under the wrong keys
    dict(fault="flip"),         # the server's update with the wrong sign
])
def test_stand_in_in_the_programs_place_is_not_correct(tmp_path, stand_in):
    """The reference put in the program's place, computed in the precision
    below bf16 or broken as named, fails the comparison; each of these
    passes the norms and is caught by the signs."""
    root = tiny.make_root(tmp_path)

    def readings(config, traffic, prog, seed):
        return harness.reference_readings(config, traffic, prog, seed,
                                          **stand_in)
    r = harness.run(root, "tiny.mix", 2 ** 31 + 3, 0.2, False,
                    time.perf_counter(),
                    harness.Hooks(**HOOKS, program_readings=readings))
    assert not r["correct"]
    assert r["checks"]["sign_gap"]["value"] > \
        r["checks"]["sign_gap"]["limit"]
