"""The dense model family: a plain float32 decoder-only transformer (its
loss and gradients), the check that a configuration describes the program's
dense model, and the training FLOPs one token costs in it.

A configuration names its family with its ``"reference"`` key; the harness
loads ``bench/reference/<reference>.py`` and uses three things of it:

- ``loss(cfg, params, tokens, quantize=identity)``: the mean next-token
  cross entropy, in float32 at HIGHEST precision, with ``quantize`` applied
  where the lower-precision control rounds;
- ``check(config, model, program_facts) -> {key: (file, program)}``: what in
  the configuration does not match the program's ``ModelCfg`` beyond the
  fields every configuration has (``bench/program.check_model``);
  ``program_facts`` holds what only the program's code states (here the
  default eps of its RMSNorm), so that the family imports nothing of it;
- ``flops_per_token(config, shapes, seq)``: training FLOPs per token, forward
  and backward, no recompute, of what one token computes in this family.

A family module imports nothing of the program. Its weights come from
``bench/inputs.py``, which draws each leaf by its name: ``ln*`` ones,
``embed`` and ``b*`` N(0, 0.02), any other leaf N(0, 1/shape[-2]). A family
names its leaves so that each gets the draw it needs.

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

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: configuration-file key -> the program's ModelCfg field it must equal
MODEL_FIELDS = {
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "attention_bias": "qkv_bias",
    "rope_theta": "rope_theta",
}

#: leaves that are matrices of a matmul in the forward pass; the tied
#: embedding is counted once, as the output head (its lookup is no matmul)
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2", "w3", "embed",
                 "lm_head")


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


def check(config, model, program_facts) -> dict:
    """What does not match between the configuration and the program's
    dense ``ModelCfg``, as ``{key: (file, program)}``."""
    wrong = {k: (config[k], getattr(model, f)) for k, f in MODEL_FIELDS.items()
             if config[k] != getattr(model, f)}
    if not math.isclose(config["attention_multiplier"], model.d_head ** -0.5):
        wrong["attention_multiplier"] = (config["attention_multiplier"],
                                         model.d_head ** -0.5)
    for k in ("embedding_multiplier", "residual_multiplier",
              "logits_scaling"):
        if config[k] != 1.0:
            wrong[k] = (config[k], 1.0)
    eps = program_facts["rms_norm_eps"]
    if not math.isclose(config["rms_norm_eps"], eps):
        wrong["rms_norm_eps"] = (config["rms_norm_eps"], eps)
    if model.family != "dense" or model.sliding_window:
        wrong["family"] = (model.family, model.sliding_window)
    return wrong


def n_matmul(shapes) -> int:
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return sum(math.prod(s.shape) for path, s in flat
               if str(getattr(path[-1], "key", "")) in MATMUL_LEAVES)


def flops_per_token(config, shapes, seq: int) -> float:
    """Training FLOPs per token, forward and backward, no recompute:
    6 N_matmul, plus causal attention's 6 S H hd per layer (QK^T and AV,
    each 2 S H hd forward for a full square, halved by the causal mask,
    times 3 for the backward)."""
    heads = config["num_attention_heads"]
    d_head = config["hidden_size"] // heads
    return 6.0 * n_matmul(shapes) \
        + 6.0 * seq * heads * d_head * config["num_hidden_layers"]
