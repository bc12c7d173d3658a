"""Named, multi-slot per-client state substrate for compression pipelines.

A stateful pipeline stage declares its persistent buffers through
``state_spec(n_coords) -> tuple[StateSlot, ...]``.  Each :class:`StateSlot`
names one buffer, gives its per-client (or shared) shape, and fixes the
engine-facing semantics the round drivers rely on:

  scope="client"   one row per client.  The engine materializes the stacked
                   ``(client_groups, n_clients) + shape`` tree ONCE
                   (``fedavg.init_server_state``), slices it per shard under
                   every cohort plan (vmap / stream / host feed / async
                   buffering), and shards it along the cohort axis under
                   ``stream(devices=D)``.
  scope="server"   one shared buffer, replicated across devices; updated in
                   the round tail (``_finish``) from the DECODED aggregate —
                   never from per-client payloads, so no dense
                   ``(n_clients, d)`` surface is ever needed.

  merge="keep"     the dead-client rule: a client that does not participate
                   in a round keeps its old rows bit-exactly (the engine
                   applies the participation mask with :func:`merge_rows`).

Slot NAMES are the keys of the state dict the pipeline passes to
``encode(key, flat, state)`` and returns from it: ``state["ef"]`` is the
error-feedback residual, ``state["cv"]`` the client control variate, and so
on.  Names must be unique across a pipeline's stages — a collision is a
build-time error (see ``Pipeline.__post_init__``), so composing two stages
that both claim a slot fails loudly instead of silently sharing a buffer.

This module is dependency-free inside the repo (jax/numpy only) so both
``core/`` and ``fed/`` layers can import it without cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

__all__ = ["StateSlot", "collect_slots", "init_tree", "merge_rows",
           "SCOPES", "MERGE_RULES"]

SCOPES = ("client", "server")
MERGE_RULES = ("keep",)


@dataclasses.dataclass(frozen=True)
class StateSlot:
    """One named persistent buffer of a stateful pipeline stage."""
    name: str
    shape: Tuple[int, ...]
    dtype: Any = jnp.float32
    scope: str = "client"
    merge: str = "keep"

    def __post_init__(self):
        if not self.name or not isinstance(self.name, str):
            raise ValueError(f"state slot needs a non-empty string name, "
                             f"got {self.name!r}")
        if self.scope not in SCOPES:
            raise ValueError(f"state slot {self.name!r}: scope must be one "
                             f"of {SCOPES}, got {self.scope!r}")
        if self.merge not in MERGE_RULES:
            raise ValueError(f"state slot {self.name!r}: merge must be one "
                             f"of {MERGE_RULES}, got {self.merge!r}")
        object.__setattr__(self, "shape", tuple(int(s) for s in self.shape))

    def zeros(self) -> jax.Array:
        return jnp.zeros(self.shape, self.dtype)


def collect_slots(stages, n_coords: int) -> Tuple[StateSlot, ...]:
    """All slots declared by ``stages`` (via ``state_spec``), in stage order.

    Raises ``ValueError`` on a slot-name collision — the loud failure that
    protects multi-state pipelines from two stages sharing a buffer.
    """
    slots, owner = [], {}
    for st in stages:
        spec = getattr(st, "state_spec", None)
        if spec is None:
            continue
        for s in spec(n_coords):
            if s.name in owner:
                raise ValueError(
                    f"state slot name collision: {s.name!r} declared by "
                    f"both {type(owner[s.name]).__name__} and "
                    f"{type(st).__name__} — slot names must be unique "
                    f"across a pipeline's stages")
            owner[s.name] = st
            slots.append(s)
    return tuple(slots)


def init_tree(slots, scope: str):
    """Zero-initialized ``{name: buffer}`` dict for one scope, or None when
    no slot has that scope (the engine's "stateless" marker)."""
    sel = {s.name: s.zeros() for s in slots if s.scope == scope}
    return sel or None


def merge_rows(new_state, old_state, mask: jax.Array):
    """Apply the merge="keep" dead-client rule over stacked state rows:
    rows of clients with ``mask > 0`` take the new value, dead clients keep
    their old rows bit-exactly.  ``mask`` has one entry per leading-axis row
    of every leaf."""
    def _merge(new, old):
        m = mask.reshape(mask.shape + (1,) * (new.ndim - mask.ndim))
        return jnp.where(m > 0, new, old)
    return jax.tree.map(_merge, new_state, old_state)
