"""Sharding hints: a tiny context the launcher sets so model code can pin
activations to the sequence-parallel layout without threading mesh/plan
through every layer.

Baseline finding that motivates this (EXPERIMENTS.md §Perf, iteration 1):
with weights TP-sharded and activations seq-sharded but *unconstrained*
inside the layer scan, GSPMD chose to all-gather the 117 MB activations every
layer and emit fp32 partial-sum all-reduces per attention chunk — 34 GB/dev
of collectives for a 0.5B model. Pinning activations (B, S, D) with S on the
`model` axis flips GSPMD to FSDP semantics: it gathers the (much smaller)
layer weights instead.

Inside the federated engine the client dimension is vmapped; the engine uses
``jax.vmap(..., spmd_axis_name=client_axes)`` so these per-client constraints
compose with the client sharding.
"""
from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.ad_checkpoint
from jax.sharding import NamedSharding, PartitionSpec as P

_CTX = {"mesh": None, "seq_axes": None, "batch_axes": None}


@contextmanager
def sharding_hints(mesh, seq_axes, batch_axes=None):
    old = dict(_CTX)
    _CTX["mesh"] = mesh
    _CTX["seq_axes"] = tuple(seq_axes) if seq_axes else None
    _CTX["batch_axes"] = tuple(batch_axes) if batch_axes else None
    try:
        yield
    finally:
        _CTX.update(old)


def _batch_entry(x, dim0_size=None):
    """Spec entry for the leading batch dim (None if not shardable)."""
    mesh, batch_axes = _CTX["mesh"], _CTX["batch_axes"]
    if mesh is None or not batch_axes:
        return None
    n = 1
    for a in batch_axes:
        n *= mesh.shape[a]
    if dim0_size is None or dim0_size % n != 0:
        return None
    return _entry(batch_axes)


def _entry(axes):
    return axes[0] if len(axes) == 1 else tuple(axes)


def seq_shard(x, seq_dim: int = 1):
    """Pin activation x (B, S, ...) to (batch-, sequence-)parallel layout.
    The batch entry matters for the big-arch plans (micro over `data`):
    an all-None batch spec would force replication of the micro dim
    (measured: 128x inflation of every activation on qwen2.5-32b)."""
    mesh, seq_axes = _CTX["mesh"], _CTX["seq_axes"]
    if mesh is None or seq_axes is None:
        return x
    if x.shape[seq_dim] % 16 != 0:
        return x
    spec = [None] * x.ndim
    spec[seq_dim] = _entry(seq_axes)
    if seq_dim != 0:
        spec[0] = _batch_entry(x, x.shape[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def fsdp_params(lp, *, skip=("w1", "w2", "w3")):
    """FSDP just-in-time weight gather for one layer's params.

    Pins every >=2D leaf (except MoE expert tensors, which stay
    expert-parallel) to a REPLICATED layout inside the layer body: GSPMD
    all-gathers the (small) weight shard instead of the (large) sequence-
    sharded activations, and the transpose in backward becomes the FSDP
    reduce-scatter of weight grads. ``skip`` names expert tensors to keep
    sharded; pass skip=() for dense layers whose w1/w2/w3 are plain MLP mats.
    """
    mesh = _CTX["mesh"]
    if mesh is None:
        return lp
    rep = NamedSharding(mesh, P())

    def maybe(path, x):
        name = str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
        if x.ndim >= 2 and name not in skip:
            # barrier pins the all-gather to the stored (bf16) dtype — XLA
            # otherwise hoists fp32 converts before the gather (2x bytes).
            # checkpoint_name lets the layer remat policy SAVE the gathered
            # copy (one gather instead of two per layer per round).
            return jax.ad_checkpoint.checkpoint_name(
                jax.lax.optimization_barrier(
                    jax.lax.with_sharding_constraint(x, rep)),
                "fsdp_gathered")
        return x

    return jax.tree_util.tree_map_with_path(maybe, lp)


def gather_seq(x):
    """Replicate a (small) tensor across the sequence axis while KEEPING the
    batch dim sharded — used for GQA K/V inside attention so GSPMD gathers
    these 16 MB bf16 tensors instead of the 235 MB fp32 queries (measured;
    EXPERIMENTS.md §Perf iteration 3)."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    spec = [None] * x.ndim
    spec[0] = _batch_entry(x, x.shape[0])
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def seq_shard_count() -> int:
    """Number of sequence shards under the current hints (1 off-mesh)."""
    mesh, seq_axes = _CTX["mesh"], _CTX["seq_axes"]
    if mesh is None or seq_axes is None:
        return 1
    n = 1
    for a in seq_axes:
        n *= mesh.shape[a]
    return n


def shard_dim(x, dim: int, axes=None):
    """Pin dim of x to the given (default: seq) axes; batch dim0 kept."""
    mesh = _CTX["mesh"]
    axes = axes if axes is not None else _CTX["seq_axes"]
    if mesh is None or axes is None:
        return x
    spec = [None] * x.ndim
    spec[dim] = _entry(tuple(axes))
    if dim != 0:
        spec[0] = _batch_entry(x, x.shape[0])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
