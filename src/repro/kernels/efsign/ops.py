"""jit'd wrapper for the fused EF-SignSGD update kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import TILE, flat_view, interpret_mode
from repro.kernels.efsign import efsign as K


def _ef_call(g: jax.Array, e: jax.Array, scale, interpret):
    flat_g = g.astype(jnp.float32).reshape(-1)
    flat_e = e.astype(jnp.float32).reshape(-1)
    pad = (-flat_g.size) % TILE
    if pad:
        flat_g = jnp.pad(flat_g, (0, pad))
        flat_e = jnp.pad(flat_e, (0, pad))
    return K.ef_update_pallas(flat_view(flat_g), flat_view(flat_e),
                              jnp.asarray(scale), interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def ef_sign_update(g: jax.Array, e: jax.Array, scale,
                   *, interpret: bool | None = None):
    """Fused EF step on arbitrary-shaped g/e. Returns (q, e_new)."""
    interpret = interpret_mode() if interpret is None else interpret
    q, e_new, _ = _ef_call(g, e, scale, interpret)
    n = g.size
    return (q.reshape(-1)[:n].reshape(g.shape),
            e_new.reshape(-1)[:n].reshape(g.shape))


@partial(jax.jit, static_argnames=("interpret",))
def ef_sign_encode(g: jax.Array, e: jax.Array, scale,
                   *, interpret: bool | None = None):
    """Fused EF encode for the flat wire codec: one VMEM pass yields BOTH the
    bitpacked uint8 payload (tile-padded; zero pad packs as +1 bits, same as
    wire.pack_flat) and the new flat residual. Returns (packed, e_new)."""
    interpret = interpret_mode() if interpret is None else interpret
    _, e_new, packed = _ef_call(g, e, scale, interpret)
    n = g.size
    return packed.reshape(-1), e_new.reshape(-1)[:n].reshape(g.shape)
