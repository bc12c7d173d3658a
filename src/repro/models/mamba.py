"""Selective SSM (Mamba-1 style) block, TPU-adapted.

GPU Mamba fuses the selective scan in a CUDA kernel; here the TPU-native
formulation is a *chunked* scan: outer ``lax.scan`` over time chunks carrying
the (B, d_inner, d_state) hidden state, inner ``lax.scan`` over steps within
the chunk, with remat per chunk — peak activation memory is one chunk of
states instead of the full sequence (see DESIGN.md §2).  Decode is the O(1)
single-step recurrence on the carried state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import _init, rms_norm

D_STATE = 16
D_CONV = 4
CHUNK = 256


def mamba_init(key, d_model: int, n_layers: int, dtype, expand: int = 2):
    d_in = expand * d_model
    ks = jax.random.split(key, 7)
    dt_rank = max(1, d_model // 16)
    return {
        "in_proj": _init(ks[0], (n_layers, d_model, 2 * d_in), dtype=dtype),
        "conv_w": _init(ks[1], (n_layers, D_CONV, d_in), scale=0.5, dtype=dtype),
        "x_proj": _init(ks[2], (n_layers, d_in, dt_rank + 2 * D_STATE), dtype=dtype),
        "dt_proj": _init(ks[3], (n_layers, dt_rank, d_in), scale=dt_rank ** -0.5, dtype=dtype),
        "dt_bias": jnp.zeros((n_layers, d_in), dtype),
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, D_STATE + 1, dtype=jnp.float32)),
            (n_layers, d_in, D_STATE)).astype(jnp.float32),
        "d_skip": jnp.ones((n_layers, d_in), jnp.float32),
        "out_proj": _init(ks[4], (n_layers, d_in, d_model), dtype=dtype),
    }


def _ssm_params(x_in, lp, dt_rank):
    """x_in: (B, T, d_in) -> dt (B,T,d_in), B_/C_ (B,T,d_state)."""
    proj = x_in @ lp["x_proj"]
    dt_low, B_, C_ = jnp.split(proj, [dt_rank, dt_rank + D_STATE], axis=-1)
    dt = jax.nn.softplus(dt_low @ lp["dt_proj"] + lp["dt_bias"])
    return dt.astype(jnp.float32), B_.astype(jnp.float32), C_.astype(jnp.float32)


def _scan_chunked(dt, B_, C_, x, a_log, h0):
    """Selective scan. dt/x: (B, T, d_in); B_/C_: (B, T, N); h0: (B, d_in, N).
    Returns y (B, T, d_in), hT."""
    Bsz, T, d_in = x.shape
    A = -jnp.exp(a_log)  # (d_in, N)
    n_chunks = max(1, T // CHUNK)
    c = T // n_chunks

    def inner_step(h, xs):
        dt_t, b_t, c_t, x_t = xs  # (B,d_in), (B,N), (B,N), (B,d_in)
        da = jnp.exp(dt_t[..., None] * A)                       # (B, d_in, N)
        h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]  # (B, d_in, N)
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    @partial(jax.checkpoint, prevent_cse=False)
    def chunk_step(h, xs):
        dt_c, b_c, c_c, x_c = xs  # (c, B, ...)
        h, y_c = jax.lax.scan(inner_step, h, (dt_c, b_c, c_c, x_c))
        return h, y_c

    def tchunks(z):
        # (B, T, ...) -> (n_chunks, c, B, ...)
        return z.reshape(Bsz, n_chunks, c, *z.shape[2:]).swapaxes(0, 1).swapaxes(1, 2)

    hT, y = jax.lax.scan(chunk_step, h0,
                         (tchunks(dt), tchunks(B_), tchunks(C_),
                          tchunks(x.astype(jnp.float32))))
    y = y.reshape(n_chunks * c, Bsz, d_in).swapaxes(0, 1)       # (B, T, d_in)
    return y, hT


def _causal_conv(x, w):
    """depthwise causal conv. x: (B, T, d_in); w: (K, d_in)."""
    pads = [(0, 0), (D_CONV - 1, 0), (0, 0)]
    xp = jnp.pad(x, pads)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(D_CONV))
    return out


def mamba_block(x, lp, *, d_model: int):
    """x: (B, T, D) -> (B, T, D). Training forward."""
    d_in = lp["in_proj"].shape[-1] // 2
    dt_rank = lp["dt_proj"].shape[0]
    if x.shape[1] > 1:
        x = jax.lax.optimization_barrier(x)
    xz = x @ lp["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = jax.nn.silu(_causal_conv(x_in, lp["conv_w"]))
    dt, B_, C_ = _ssm_params(x_in, lp, dt_rank)
    h0 = jnp.zeros((x.shape[0], d_in, D_STATE), jnp.float32)
    y, _ = _scan_chunked(dt, B_, C_, x_in, lp["a_log"], h0)
    y = y + x_in.astype(jnp.float32) * lp["d_skip"]
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    return y @ lp["out_proj"]


def mamba_cache_init(batch: int, d_model: int, n_layers: int, expand: int = 2):
    d_in = expand * d_model
    return {"h": jnp.zeros((n_layers, batch, d_in, D_STATE), jnp.float32),
            "conv": jnp.zeros((n_layers, batch, D_CONV - 1, d_in), jnp.float32)}


def mamba_decode_step(x, lp, h, conv_tail, *, d_model: int):
    """One-token recurrence. x: (B, 1, D); h: (B, d_in, N);
    conv_tail: (B, D_CONV-1, d_in). Returns (y, h, conv_tail)."""
    d_in = lp["in_proj"].shape[-1] // 2
    dt_rank = lp["dt_proj"].shape[0]
    xz = x @ lp["in_proj"]
    x_in, z = jnp.split(xz, 2, axis=-1)                         # (B, 1, d_in)
    window = jnp.concatenate([conv_tail, x_in.astype(jnp.float32)], axis=1)
    conv_out = jnp.einsum("bkd,kd->bd", window, lp["conv_w"].astype(jnp.float32))
    x_c = jax.nn.silu(conv_out)[:, None, :]                     # (B, 1, d_in)
    dt, B_, C_ = _ssm_params(x_c.astype(x.dtype), lp, dt_rank)
    A = -jnp.exp(lp["a_log"])
    da = jnp.exp(dt[:, 0, :, None] * A)
    h = da * h + (dt[:, 0] * x_c[:, 0].astype(jnp.float32))[..., None] * B_[:, 0, None, :]
    y = jnp.einsum("bdn,bn->bd", h, C_[:, 0])
    y = y + x_c[:, 0].astype(jnp.float32) * lp["d_skip"]
    y = (y[:, None, :].astype(x.dtype)) * jax.nn.silu(z)
    return y @ lp["out_proj"], h, window[:, 1:]
