"""A plain float32 decoder-only transformer: loss and gradients.

The dense architecture of Qwen2 and Granite 3.0 as their papers and
``config.json`` describe it: token embedding (times ``embedding_multiplier``),
then per layer a pre-RMSNorm block of grouped-query attention with rotary
position embedding (rotate-half form, base ``rope_theta``), optional QKV
bias and scores scaled by ``attention_multiplier``, and a pre-RMSNorm SwiGLU
MLP, each added to the residual stream times ``residual_multiplier``; a
final RMSNorm, logits from the tied embedding divided by ``logits_scaling``,
and the mean next-token cross entropy.

Every value is float32 and every contraction runs at HIGHEST precision.
``quantize`` rounds the two inputs of each contraction and the residual
stream after each addition: the identity for the reference, or the
lower-precision control's rounding, at the points where a bf16 program holds
its values in bf16. Parameters come in the
layout of the weights file the benchmark writes: stacked over depth under
``attn``/``mlp``/``ln1``/``ln2``, with ``embed`` and ``lnf``.

Departures from the papers: none in the equations. The constants are read
from the configuration file as it is run (see its ``reduced`` list).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def identity(x):
    return x


def _mm(spec, a, b, quantize):
    return jnp.einsum(spec, quantize(a), quantize(b), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x: (B, S, H, hd), rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(cfg, x, lp, quantize):
    """One layer. x: (B, S, D) f32; lp: this layer's parameters."""
    B, S, _ = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"], eps)
    q = _mm("bsd,de->bse", h, a["wq"], quantize)
    k = _mm("bsd,de->bse", h, a["wk"], quantize)
    v = _mm("bsd,de->bse", h, a["wv"], quantize)
    if cfg["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(B, S, H, hd), cfg["rope_theta"])
    k = rope(k.reshape(B, S, K, hd), cfg["rope_theta"])
    v = v.reshape(B, S, K, hd)
    # grouped-query attention: query head n reads key/value head n // (H/K)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    scores = _mm("bqhd,bkhd->bhqk", q, k, quantize) \
        * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", probs, v, quantize).reshape(B, S, H * hd)
    x = quantize(x + res * _mm("bse,ed->bsd", o, a["wo"], quantize))
    h = rms_norm(x, lp["ln2"], eps)
    m = lp["mlp"]
    gate = _mm("bsd,df->bsf", h, m["w1"], quantize)
    up = _mm("bsd,df->bsf", h, m["w3"], quantize)
    y = _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w2"], quantize)
    return quantize(x + res * y)


def loss(cfg, params, tokens, quantize=identity):
    """Mean next-token cross entropy of ``tokens`` (B, S) int32 under f32
    ``params``."""
    embed = params["embed"]
    x = quantize(embed[tokens] * cfg["embedding_multiplier"])
    stacked = {"attn": params["attn"], "mlp": params["mlp"],
               "ln1": params["ln1"], "ln2": params["ln2"]}

    @jax.checkpoint
    def layer(x, lp):
        return block(cfg, x, lp, quantize), None

    x, _ = jax.lax.scan(layer, x, stacked)
    x = rms_norm(x, params["lnf"], cfg["rms_norm_eps"])
    logits = _mm("bsd,vd->bsv", x[:, :-1], embed, quantize) \
        / cfg["logits_scaling"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)
