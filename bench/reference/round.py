"""A plain reference of one z-SignFedAvg round, and of the first rounds of a
run.

A round, as the paper's Algorithm 1 states it: the server splits its key
and hands client j the key fold_in(round key, j). Every live client runs E
local SGD steps from the server weights, x <- x - gamma * grad, and sends
the sign of its pseudo-gradient (x_0 - x_E) / gamma under z-noise of scale
sigma (``threefry``: one bit per coordinate). The server sums the +-1 signs
of the live clients, and steps x <- x - eta * gamma * eta_z * sigma *
sum / n_live, where eta_z = 2^{1/(2z)} Gamma(1 + 1/(2z)) debiases the sign.

The model is the ``loss`` of the configuration's family module
(``bench/reference/<family>.py``), which the caller hands in. All
arithmetic is float32 at HIGHEST precision. The weights are
held between steps in the dtype the configuration states (bfloat16): each
local step and the server step round their result to it, as a deployment
that keeps bf16 weights does. Departures from the program's arithmetic,
which the comparison's limits absorb: the program computes the model in
bf16, rounds ``gamma * grad`` to bf16 before subtracting it, and evaluates
the Gaussian CDF through a rational erf; the bit of a coordinate whose
pseudo-gradient differs by a rounding step can therefore differ.

``precision="fp8"`` turns this into the lower-precision control: the
inputs of every contraction and the residual stream are rounded to float8
e4m3, with one scale per tensor (``fp8``), while the weights stay held in
bf16 between steps, as a bf16 program that moved its matmuls to fp8 would.
``contributing`` plants a fault of a broken program: clients whose sign
never reaches the server sum (the exchange between chips left out), while
their loss and n_live still count. ``fault`` plants another: ``"keys"``
encodes each client under a key that is not the round's (fold_in of the
round key with j + clients in place of j), ``"flip"`` applies the server's
update with the wrong sign.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.compare import change_signs, leaf_norms
from bench.reference import threefry


class Setting(NamedTuple):
    clients: int
    local_steps: int
    client_lr: float
    server_lr: float
    sigma: float
    z: int              # 1 = Gaussian noise, 0 = uniform (z = infinity)
    store: object       # dtype the weights are held in between steps


def eta_z(z: int) -> float:
    if z <= 0:
        return 1.0
    return 2.0 ** (1.0 / (2 * z)) * math.gamma(1.0 + 1.0 / (2 * z))


def cdf(r, z: int):
    """P(r + xi >= 0) for z-noise xi: Phi(r) for z=1, uniform for z=inf."""
    if z == 1:
        return jax.scipy.special.ndtr(r)
    if z <= 0:
        return jnp.clip(0.5 * (r + 1.0), 0.0, 1.0)
    raise ValueError(f"the reference covers z=1 and z=inf, not z={z}")


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax to 448), and
    pass gradients straight through: a contraction computed in fp8."""
    return _fp8(x)


@jax.custom_vjp
def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def identity(x):
    return x


def _precision(name: str, store_dtype):
    """-> (rounding of contraction inputs and activations, rounding of the
    weights between steps)."""
    if name == "f32":
        return identity, lambda w: w.astype(store_dtype)
    if name == "fp8":
        return fp8, lambda w: w.astype(store_dtype)
    raise ValueError(f"unknown precision {name!r}")


def client_keys(round_key, n: int):
    return jax.vmap(lambda j: jax.random.fold_in(round_key, j))(
        jnp.arange(n, dtype=jnp.uint32))


class Reference:
    """The round above for one configuration, its family module and a
    setting."""

    def __init__(self, family, cfg: dict, setting: Setting,
                 precision: str = "f32", fault: str | None = None):
        if fault not in (None, "keys", "flip"):
            raise ValueError(f"unknown fault {fault!r}")
        self.cfg, self.s, self.fault = cfg, setting, fault
        q, store = _precision(precision, setting.store)
        s = setting

        def loss(p, tokens):
            p32 = jax.tree.map(lambda w: w.astype(jnp.float32), p)
            return family.loss(cfg, p32, tokens, q)

        def client(x0, counts, tokens, key, weight):
            def step(x, tok):
                value, g = jax.value_and_grad(loss)(x, tok)
                x = jax.tree.map(
                    lambda w, gw: store(w.astype(jnp.float32)
                                        - s.client_lr * gw), x, g)
                return x, value

            x_e, losses = jax.lax.scan(step, x0, tokens)
            out, offset = [], 0
            for c, a, b in zip(jax.tree_util.tree_leaves(counts),
                               jax.tree_util.tree_leaves(x0),
                               jax.tree_util.tree_leaves(x_e)):
                pseudo = (a.astype(jnp.float32) - b.astype(jnp.float32)) \
                    / s.client_lr
                index = jnp.uint32(offset) + jnp.arange(
                    a.size, dtype=jnp.uint32).reshape(a.shape)
                u = threefry.uniforms(key, index)
                bit = u > 1.0 - cdf(pseudo / s.sigma, s.z)
                out.append(c + jnp.where(bit, weight, -weight).astype(c.dtype))
                offset += a.size
            return (jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(counts), out),
                jnp.mean(losses))

        def server(x0, counts, n_live):
            step = s.server_lr * s.client_lr * eta_z(s.z) * s.sigma / n_live
            if fault == "flip":
                step = -step
            return jax.tree.map(
                lambda w, c: store(w.astype(jnp.float32)
                                   - step * c.astype(jnp.float32)),
                x0, counts)

        self._client = jax.jit(client, donate_argnums=(1,))
        self._server = jax.jit(server)
        self._store = jax.jit(lambda p: jax.tree.map(store, p))
        self._norms = leaf_norms

    def round(self, params, tokens, round_key, mask, contributing=None):
        """One round. tokens: (clients, E, micro, seq); mask: (clients,) 0/1
        participation. Returns (new params, mean client loss)."""
        mask = np.asarray(mask, np.float32)
        contributing = mask if contributing is None else \
            np.asarray(contributing, np.float32) * mask
        n = self.s.clients
        keys = client_keys(round_key, 2 * n)[n:] if self.fault == "keys" \
            else client_keys(round_key, n)
        if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
            keys = jax.random.key_data(keys)
        counts = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.int16), params)
        loss_sum = 0.0
        for j in range(self.s.clients):
            if mask[j] == 0:
                continue
            counts, loss = self._client(params, counts, tokens[j], keys[j],
                                        jnp.int16(contributing[j] > 0))
            loss_sum += float(loss)
        n_live = max(float(mask.sum()), 1.0)
        return self._server(params, counts, jnp.float32(n_live)), \
            loss_sum / n_live

    def run(self, params0, tokens_fn, server_key, mask, rounds: int,
            signs_key, contributing=None) -> dict:
        """The first ``rounds`` rounds from ``params0``: each round's loss,
        the per-leaf norms of the first round's change (the first gradient
        as the server optimizer gets it, times its learning rate) and of the
        change after all of them, and the signs of the first round's change
        at the coordinates ``signs_key`` draws. ``tokens_fn(t)`` gives round
        t's tokens as (clients, E, micro, seq)."""
        rng, params, losses = server_key, self._store(params0), []
        for t in range(rounds):
            rng, sub = jax.random.split(rng)
            params, loss = self.round(params, tokens_fn(t), sub, mask,
                                      contributing)
            losses.append(loss)
            if t == 0:
                grad = np.asarray(self._norms(params, params0))
                sign = change_signs(params, params0, signs_key)
        change = np.asarray(self._norms(params, params0))
        return {"loss": losses, "grad": grad.tolist(),
                "change": change.tolist(), "sign": sign}
