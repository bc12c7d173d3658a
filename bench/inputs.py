"""What a run feeds the system, made from ``--seed`` alone: the weights, every
round's tokens, and the server's key. The program and the reference both
take them from here, so neither takes anything the other made.

Seeds may exceed 32 bits: the key is the seed's two 32-bit words.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

WEIGHTS, SERVER, DATA, SIGNS = 0, 1, 2, 3


def base_key(seed: int) -> jax.Array:
    seed = int(seed) % 2 ** 64
    return jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def stream_key(seed: int, stream: int) -> jax.Array:
    return jax.random.fold_in(base_key(seed), stream)


def server_key(seed: int) -> jax.Array:
    """The server state's raw uint32[2] key, from which every round's
    client keys derive."""
    return stream_key(seed, SERVER)


def _leaf(key, name: str, shape, dtype):
    if name.startswith("ln"):
        return jnp.ones(shape, dtype)
    if name == "embed" or name.startswith("b"):
        scale = 0.02
    else:
        scale = 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@functools.lru_cache(maxsize=None)
def _params_fn(treedef, specs):
    def make(key):
        leaves = [_leaf(jax.random.fold_in(key, i), name, shape, dtype)
                  for i, (name, shape, dtype) in enumerate(specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return jax.jit(make)


def params(shapes, seed: int):
    """Weights shaped like ``shapes`` (a pytree of ShapeDtypeStruct, e.g.
    ``jax.eval_shape`` of the program's init), made on the device in one
    jitted call: N(0, 1/fan_in) projections, N(0, 0.02) embedding and
    biases, ones for the norm scales (leaves named ``ln*``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = tuple((str(getattr(path[-1], "key", path[-1])), tuple(s.shape),
                   jnp.dtype(s.dtype)) for path, s in flat)
    return _params_fn(treedef, specs)(stream_key(seed, WEIGHTS))


@functools.lru_cache(maxsize=None)
def _tokens_fn(shape, vocab):
    return jax.jit(lambda key, t: jax.random.randint(
        jax.random.fold_in(key, t), shape, 0, vocab, jnp.int32))


def data_key(seed: int) -> jax.Array:
    return stream_key(seed, DATA)


def signs_key(seed: int) -> jax.Array:
    """The key that draws the coordinates ``compare.change_signs`` reads."""
    return stream_key(seed, SIGNS)


def tokens(key: jax.Array, t: int, shape: tuple, vocab: int) -> jax.Array:
    """Round t's tokens under ``data_key(seed)``, uniform over the
    vocabulary, of ``shape``."""
    return _tokens_fn(tuple(shape), int(vocab))(key, jnp.uint32(t))
