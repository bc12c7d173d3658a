"""Work counts from shapes: model FLOPs per token and the least bytes of the
codec kernels. The per-layer shares divide these by measured time, so none
of them can pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import math

import jax

#: leaves that are matrices of a matmul in the forward pass; the tied
#: embedding is counted once, as the output head (its lookup is no matmul)
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2", "w3", "embed",
                 "lm_head")


def n_params(shapes) -> int:
    return sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))


def n_matmul(shapes) -> int:
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return sum(math.prod(s.shape) for path, s in flat
               if str(getattr(path[-1], "key", "")) in MATMUL_LEAVES)


def flops_per_token(shapes, n_layers: int, n_heads: int, d_head: int,
                    seq: int) -> float:
    """Training FLOPs per token, forward and backward, no recompute:
    6 N_matmul, plus causal attention's 6 S H hd per layer (QK^T and AV,
    each 2 S H hd forward for a full square, halved by the causal mask,
    times 3 for the backward)."""
    return 6.0 * n_matmul(shapes) + 6.0 * seq * n_heads * d_head * n_layers


def encode_bytes(clients: int, d: int) -> float:
    """Least HBM bytes of one round's client encodes: read the f32
    pseudo-gradient, write one bit per coordinate."""
    return clients * (4.0 * d + d / 8.0)


def reduce_bytes(live: int, d: int) -> float:
    """Least HBM bytes of one round's server sign-reduce: read every live
    client's payload, write the f32 sum once."""
    return live * d / 8.0 + 4.0 * d
