"""Work counts from shapes: the parameter count and the least bytes of the
codec kernels, the same for every model family (a family's FLOPs per token
are its module's, ``bench/reference/<family>.py``). The per-layer shares
divide these by measured time, so none of them can pass 100% unless the
time leaves out part of the work.
"""
from __future__ import annotations

import math

import jax


def n_params(shapes) -> int:
    return sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))


def encode_bytes(clients: int, d: int) -> float:
    """Least HBM bytes of one round's client encodes: read the f32
    pseudo-gradient, write one bit per coordinate."""
    return clients * (4.0 * d + d / 8.0)


def reduce_bytes(live: int, d: int) -> float:
    """Least HBM bytes of one round's server sign-reduce: read every live
    client's payload, write the f32 sum once."""
    return live * d / 8.0 + 4.0 * d
