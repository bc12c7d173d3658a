"""Decoder-only transformer LM (dense or MoE), GQA + RoPE + optional SWA.

Params are stacked over depth; forward is lax.scan over layers with
jax.checkpoint (remat) per layer.  Provides train loss and one-token decode.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import layers as L


def _remat_policy(cfg):
    return jax.checkpoint_policies.save_only_these_names("attn_kv")


def init_params(key, cfg) -> Dict[str, Any]:
    ks = jax.random.split(key, 6)
    D, V, nl = cfg.d_model, cfg.vocab, cfg.n_layers
    dtype = cfg.dtype
    p = {
        "embed": L._init(ks[0], (V, D), scale=0.02, dtype=dtype),
        "attn": L.attn_init(ks[1], cfg.attn_cfg(), nl, dtype),
        "ln1": jnp.ones((nl, D), dtype),
        "ln2": jnp.ones((nl, D), dtype),
        "lnf": jnp.ones((D,), dtype),
    }
    if cfg.moe_experts > 0:
        p["moe"] = L.moe_init(ks[2], D, cfg.d_ff, cfg.moe_experts, nl, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[2], D, cfg.d_ff, nl, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = L._init(ks[3], (D, V), scale=0.02, dtype=dtype)
    return p


def _layer(cfg, x, lp, positions):
    """One transformer block. x: (B, S, D)."""
    h = x + L.attention(L.rms_norm(x, lp["ln1"]), lp["attn"], cfg.attn_cfg(), positions)
    hn = L.rms_norm(h, lp["ln2"])
    if cfg.moe_experts > 0:
        y, aux = L.moe_apply(hn, lp["moe"], cfg.moe_experts, cfg.moe_topk,
                             ep=cfg.moe_ep)
    else:
        y, aux = L.swiglu(hn, lp["mlp"]), 0.0
    return h + y, aux


def _stacked_layer_params(params, cfg):
    lp = {"attn": params["attn"], "ln1": params["ln1"], "ln2": params["ln2"]}
    lp["moe" if cfg.moe_experts > 0 else "mlp"] = params[
        "moe" if cfg.moe_experts > 0 else "mlp"]
    return lp


def forward_hidden(params, tokens, cfg, *, embeds: jnp.ndarray | None = None):
    """Returns final-norm hidden states (B, S, D) and MoE aux loss."""
    x = params["embed"][tokens] if embeds is None else embeds.astype(cfg.dtype)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    lp_stack = _stacked_layer_params(params, cfg)

    @partial(jax.checkpoint, prevent_cse=False,
             policy=_remat_policy(cfg))
    def body(carry, lp):
        x, aux = carry
        x, a = _layer(cfg, x, lp, positions)
        return (x, aux + a), ()

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), lp_stack)
    return L.rms_norm(x, params["lnf"]), aux / cfg.n_layers


def lm_head(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, tokens, cfg, *, embeds: jnp.ndarray | None = None):
    """Full logits (tests / small shapes only — O(S*V) memory)."""
    x, aux = forward_hidden(params, tokens, cfg, embeds=embeds)
    return (x @ lm_head(params, cfg)).astype(jnp.float32), aux


def loss_fn(params, batch, cfg):
    """Next-token CE, sequence-chunked (never materializes full logits)."""
    tokens = batch["tokens"]
    embeds = batch.get("embeds")
    x, aux = forward_hidden(params, tokens, cfg, embeds=embeds)
    mask = batch.get("loss_mask")
    mask = mask[:, 1:].astype(jnp.float32) if mask is not None else None
    ce = L.chunked_ce(x[:, :-1], lm_head(params, cfg), tokens[:, 1:], mask,
                      chunk=cfg.q_chunk)
    return ce + 0.01 * aux


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg, batch_size: int, max_len: int):
    K, hd, nl = cfg.n_kv_heads, cfg.d_head, cfg.n_layers
    kv_dtype = cfg.dtype
    return {"k": jnp.zeros((nl, batch_size, max_len, K, hd), kv_dtype),
            "v": jnp.zeros((nl, batch_size, max_len, K, hd), kv_dtype)}


def decode_step(params, cache, tokens, position, cfg):
    """One decode step. tokens: (B, 1) int32; position: scalar int32.
    Returns (logits (B, 1, V), new_cache)."""
    x = params["embed"][tokens]
    lp_stack = _stacked_layer_params(params, cfg)

    def body(x, scanned):
        lp, ck, cv = scanned
        y, ck, cv = L.attention_decode(L.rms_norm(x, lp["ln1"]), lp["attn"],
                                       cfg.attn_cfg(), ck, cv, position)
        h = x + y
        hn = L.rms_norm(h, lp["ln2"])
        if cfg.moe_experts > 0:
            y, _ = L.moe_apply(hn, lp["moe"], cfg.moe_experts, cfg.moe_topk,
                               ep=cfg.moe_ep)
        else:
            y = L.swiglu(hn, lp["mlp"])
        return h + y, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(body, x, (lp_stack, cache["k"], cache["v"]))
    x = L.rms_norm(x, params["lnf"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32), {"k": new_k, "v": new_v}
