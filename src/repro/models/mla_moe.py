"""Decoder-only LM of the DeepSeek-V3 layout (Moonlight-16B-A3B): latent
attention (MLA) in every layer, ``n_dense_layers`` leading layers with a
dense SwiGLU FFN, then expert layers of sigmoid-routed experts of which the
chip holds a share, beside shared experts.

Params are stacked over depth per kind of layer (``dense``, ``moe``); the
forward is one ``lax.scan`` over each stack, each layer rematerialised.
The loss is the next-token cross entropy plus ``moe_aux_alpha`` times the
sum over expert layers of the sequence-wise balance loss.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import layers as L

#: the stacks, in the order the forward runs them
KINDS = ("dense", "moe")


def _depths(cfg) -> dict:
    return {"dense": cfg.n_dense_layers,
            "moe": cfg.n_layers - cfg.n_dense_layers}


def init_params(key, cfg) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    D, V, dtype = cfg.d_model, cfg.vocab, cfg.dtype
    p = {"embed": L._init(ks[0], (V, D), scale=0.02, dtype=dtype),
         "lnf": jnp.ones((D,), dtype)}
    for i, (kind, n) in enumerate(_depths(cfg).items()):
        if n == 0:
            continue
        ka, kf = jax.random.split(ks[1 + i])
        lp = {"attn": L.mla_init(ka, cfg.mla_cfg(), n, dtype),
              "ln1": jnp.ones((n, D), dtype), "ln2": jnp.ones((n, D), dtype)}
        if kind == "dense":
            lp["mlp"] = L.mlp_init(kf, D, cfg.d_ff, n, dtype)
        else:
            lp.update(L.experts_init(kf, cfg.expert_cfg(), n, dtype))
        p[kind] = lp
    if not cfg.tie_embeddings:
        p["lm_head"] = L._init(ks[3], (D, V), scale=0.02, dtype=dtype)
    return p


def _ffn(cfg, kind, x, lp):
    if kind == "dense":
        return L.swiglu(x, lp["mlp"]), jnp.zeros((), jnp.float32)
    return L.routed_experts(x, lp, cfg.expert_cfg())


def _layer(cfg, kind, x, lp, positions):
    eps = cfg.rms_eps
    h = x + L.mla_attention(L.rms_norm(x, lp["ln1"], eps), lp["attn"],
                            cfg.mla_cfg(), positions)
    y, aux = _ffn(cfg, kind, L.rms_norm(h, lp["ln2"], eps), lp)
    return h + y, aux


def forward_hidden(params, tokens, cfg):
    """-> final-norm hidden states (B, S, D), summed balance loss."""
    x = params["embed"][tokens]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    aux = jnp.zeros((), jnp.float32)
    for kind in KINDS:
        if kind not in params:
            continue

        @partial(jax.checkpoint, prevent_cse=False)
        def body(carry, lp, kind=kind):
            x, aux = carry
            x, a = _layer(cfg, kind, x, lp, positions)
            return (x, aux + a), ()

        (x, aux), _ = jax.lax.scan(body, (x, aux), params[kind])
    return L.rms_norm(x, params["lnf"], cfg.rms_eps), aux


def lm_head(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def loss_fn(params, batch, cfg):
    tokens = batch["tokens"]
    x, aux = forward_hidden(params, tokens, cfg)
    ce = L.chunked_ce(x[:, :-1], lm_head(params, cfg), tokens[:, 1:],
                      chunk=cfg.q_chunk)
    return ce + cfg.moe_aux_alpha * aux


# ---------------------------------------------------------------------------
# decode through the latent cache
# ---------------------------------------------------------------------------

def init_cache(cfg, batch_size: int, max_len: int):
    return {kind: {"c_kv": jnp.zeros((n, batch_size, max_len,
                                      cfg.kv_lora_rank), cfg.dtype),
                   "k_pe": jnp.zeros((n, batch_size, max_len,
                                      cfg.qk_rope_dim), cfg.dtype)}
            for kind, n in _depths(cfg).items() if n}


def decode_step(params, cache, tokens, position, cfg):
    """One decode step. tokens: (B, 1) int32; position: scalar int32.
    Returns (logits (B, 1, V), new cache)."""
    x = params["embed"][tokens]
    eps = cfg.rms_eps
    new = {}
    for kind in KINDS:
        if kind not in params:
            continue

        def body(x, scanned, kind=kind):
            lp, c, pe = scanned
            y, c, pe = L.mla_decode(L.rms_norm(x, lp["ln1"], eps), lp["attn"],
                                    cfg.mla_cfg(), c, pe, position)
            h = x + y
            y, _ = _ffn(cfg, kind, L.rms_norm(h, lp["ln2"], eps), lp)
            return h + y, (c, pe)

        x, (c, pe) = jax.lax.scan(
            body, x, (params[kind], cache[kind]["c_kv"], cache[kind]["k_pe"]))
        new[kind] = {"c_kv": c, "k_pe": pe}
    x = L.rms_norm(x, params["lnf"], eps)
    return (x @ lm_head(params, cfg)).astype(jnp.float32), new
