"""Jamba-style hybrid: attention:mamba 1:7 interleave, MoE on alternate
sublayers (matching Jamba-1.5's every-other-layer MoE; 4 MoE + 4 dense FFN
per 8-sublayer super-block -> 36 MoE layers at 72 total).

Params are stacked over super-blocks (n_layers // 8) and scanned; the 8
sublayers inside a super-block are unrolled (attn at position 0, mamba at
1..7), so HLO size is O(8) regardless of depth.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba as M

SUB = 8  # sublayers per super-block: 1 attn + 7 mamba


def _remat_policy(cfg):
    return jax.checkpoint_policies.save_only_these_names("attn_kv")


def init_params(key, cfg):
    nb = cfg.n_layers // SUB
    D, V, dtype = cfg.d_model, cfg.vocab, cfg.dtype
    ks = jax.random.split(key, 8)
    n_moe, n_mlp = SUB // 2, SUB - SUB // 2
    p = {
        "embed": L._init(ks[0], (V, D), scale=0.02, dtype=dtype),
        "attn": L.attn_init(ks[1], cfg.attn_cfg(), nb, dtype),
        "mamba": M.mamba_init(ks[2], D, nb * (SUB - 1), dtype),
        "moe": L.moe_init(ks[3], D, cfg.d_ff, cfg.moe_experts, nb * n_moe, dtype),
        "mlp": L.mlp_init(ks[4], D, cfg.d_ff, nb * n_mlp, dtype),
        "ln_mix": jnp.ones((nb, SUB, D), dtype),
        "ln_ffn": jnp.ones((nb, SUB, D), dtype),
        "lnf": jnp.ones((D,), dtype),
    }
    # restack per super-block: mamba (nb, 7, ...), moe (nb, 4, ...), mlp (nb, 4, ...)
    p["mamba"] = jax.tree.map(lambda w: w.reshape(nb, SUB - 1, *w.shape[1:]), p["mamba"])
    p["moe"] = jax.tree.map(lambda w: w.reshape(nb, n_moe, *w.shape[1:]), p["moe"])
    p["mlp"] = jax.tree.map(lambda w: w.reshape(nb, n_mlp, *w.shape[1:]), p["mlp"])
    if not cfg.tie_embeddings:
        p["lm_head"] = L._init(ks[5], (D, V), scale=0.02, dtype=dtype)
    return p


def _super_block(cfg, x, bp, positions):
    """8 sublayers: [attn, mamba x7]; FFN alternates MoE (even) / MLP (odd)."""
    aux = jnp.zeros((), jnp.float32)
    moe_i = mlp_i = 0
    for s in range(SUB):
        xn = L.rms_norm(x, bp["ln_mix"][s])
        if s == 0:
            mix = L.attention(xn, bp["attn"], cfg.attn_cfg(), positions)
        else:
            lp = jax.tree.map(lambda w: w[s - 1], bp["mamba"])
            mix = M.mamba_block(xn, lp, d_model=cfg.d_model)
        x = x + mix
        hn = L.rms_norm(x, bp["ln_ffn"][s])
        if s % 2 == 0:
            lp = jax.tree.map(lambda w: w[moe_i], bp["moe"])
            y, a = L.moe_apply(hn, lp, cfg.moe_experts, cfg.moe_topk,
                               ep=cfg.moe_ep)
            aux += a
            moe_i += 1
        else:
            lp = jax.tree.map(lambda w: w[mlp_i], bp["mlp"])
            y = L.swiglu(hn, lp)
            mlp_i += 1
        x = x + y
    return x, aux


def forward_hidden(params, tokens, cfg):
    x = params["embed"][tokens]
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    stack = {k: params[k] for k in ("attn", "mamba", "moe", "mlp", "ln_mix", "ln_ffn")}

    @partial(jax.checkpoint, prevent_cse=False,
             policy=_remat_policy(cfg))
    def body(carry, bp):
        x, aux = carry
        x, a = _super_block(cfg, x, bp, positions)
        return (x, aux + a), ()

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), stack)
    return L.rms_norm(x, params["lnf"]), aux / (cfg.n_layers // 2)


def _head(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, tokens, cfg):
    x, aux = forward_hidden(params, tokens, cfg)
    return (x @ _head(params, cfg)).astype(jnp.float32), aux


def loss_fn(params, batch, cfg):
    x, aux = forward_hidden(params, batch["tokens"], cfg)
    ce = L.chunked_ce(x[:, :-1], _head(params, cfg), batch["tokens"][:, 1:],
                      chunk=cfg.q_chunk)
    return ce + 0.01 * aux


def init_cache(cfg, batch_size: int, max_len: int):
    nb = cfg.n_layers // SUB
    K, hd = cfg.n_kv_heads, cfg.d_head
    mc = M.mamba_cache_init(batch_size, cfg.d_model, nb * (SUB - 1))
    return {
        "k": jnp.zeros((nb, batch_size, max_len, K, hd), cfg.dtype),
        "v": jnp.zeros((nb, batch_size, max_len, K, hd), cfg.dtype),
        "h": mc["h"].reshape(nb, SUB - 1, *mc["h"].shape[1:]),
        "conv": mc["conv"].reshape(nb, SUB - 1, *mc["conv"].shape[1:]),
    }


def decode_step(params, cache, tokens, position, cfg):
    x = params["embed"][tokens]
    stack = {k: params[k] for k in ("attn", "mamba", "moe", "mlp", "ln_mix", "ln_ffn")}

    def body(x, scanned):
        bp, ck, cv, h, conv = scanned
        moe_i = mlp_i = 0
        new_h, new_conv = [], []
        for s in range(SUB):
            xn = L.rms_norm(x, bp["ln_mix"][s])
            if s == 0:
                mix, ck, cv = L.attention_decode(xn, bp["attn"], cfg.attn_cfg(),
                                                 ck, cv, position)
            else:
                lp = jax.tree.map(lambda w: w[s - 1], bp["mamba"])
                mix, h_s, conv_s = M.mamba_decode_step(
                    xn, lp, h[s - 1], conv[s - 1], d_model=cfg.d_model)
                new_h.append(h_s)
                new_conv.append(conv_s)
            x = x + mix
            hn = L.rms_norm(x, bp["ln_ffn"][s])
            if s % 2 == 0:
                lp = jax.tree.map(lambda w: w[moe_i], bp["moe"])
                y, _ = L.moe_apply(hn, lp, cfg.moe_experts, cfg.moe_topk,
                                   ep=cfg.moe_ep)
                moe_i += 1
            else:
                lp = jax.tree.map(lambda w: w[mlp_i], bp["mlp"])
                y = L.swiglu(hn, lp)
                mlp_i += 1
            x = x + y
        return x, (ck, cv, jnp.stack(new_h), jnp.stack(new_conv))

    x, (nk, nv, nh, nconv) = jax.lax.scan(
        body, x, (stack, cache["k"], cache["v"], cache["h"], cache["conv"]))
    x = L.rms_norm(x, params["lnf"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32), {"k": nk, "v": nv, "h": nh, "conv": nconv}
