"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, parallel train
form) and sLSTM (scalar memory, strictly recurrent).

mLSTM training uses the attention-like parallel formulation with a
stabilized log-gate decay matrix (quadratic in the chunk, chunked over
sequence); decode is the O(1) matrix-memory recurrence.  sLSTM trains with a
chunked sequential scan (no parallel form exists — paper's own statement).
The assigned xlstm-350m config (d_ff = 0) means blocks carry their own
up/down projections (proj factor 2), no separate FFN — noted in DESIGN.md.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.models.layers import _init, rms_norm

CHUNK = 256


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(key, d_model: int, n_heads: int, n_layers: int, dtype):
    ks = jax.random.split(key, 6)
    return {
        "wqkv": _init(ks[0], (n_layers, d_model, 3 * d_model), dtype=dtype),
        "wif": _init(ks[1], (n_layers, d_model, 2 * n_heads), scale=0.02, dtype=dtype),
        "bif": jnp.zeros((n_layers, 2 * n_heads), jnp.float32),
        "wo": _init(ks[2], (n_layers, d_model, d_model), dtype=dtype),
        "ln_sk": jnp.ones((n_layers, d_model), dtype),
    }


def _mlstm_gates(x, lp, n_heads):
    gif = x.astype(jnp.float32) @ lp["wif"].astype(jnp.float32) + lp["bif"]
    i_pre, f_pre = jnp.split(gif, 2, axis=-1)         # (B, T, H)
    log_f = -jax.nn.softplus(-f_pre)                  # log sigmoid(f)
    return i_pre, log_f


def mlstm_block(x, lp, *, n_heads: int):
    """Parallel (chunk-quadratic) mLSTM forward. x: (B, T, D)."""
    B, T, D = x.shape
    H, hd = n_heads, D // n_heads
    qkv = x @ lp["wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, hd).swapaxes(1, 2)         # (B, H, T, hd)
    k = k.reshape(B, T, H, hd).swapaxes(1, 2) / (hd ** 0.5)
    v = v.reshape(B, T, H, hd).swapaxes(1, 2)
    i_pre, log_f = _mlstm_gates(x, lp, H)             # (B, T, H)
    i_pre = i_pre.swapaxes(1, 2)                      # (B, H, T)
    log_f = log_f.swapaxes(1, 2)
    F = jnp.cumsum(log_f, axis=-1)                    # (B, H, T) log prod f

    # D[t,s] = exp(F_t - F_s + i_s), s <= t. Flash-style: chunk over the
    # KEY axis with an online running max, as layers._flash_kv_attention.
    kc = min(CHUNK, T)
    if T % kc != 0:
        kc = T
    nc = T // kc
    qf = q.astype(jnp.float32)                        # (B, H, T, hd)
    k_g, v_g, F_g, i_g = jax.lax.optimization_barrier(
        (k.swapaxes(1, 2),                            # (B, T, H, hd)
         v.swapaxes(1, 2),
         F.swapaxes(1, 2),                            # (B, T, H)
         i_pre.swapaxes(1, 2)))
    kt = k_g.reshape(B, nc, kc, H, hd).swapaxes(0, 1)
    vt = v_g.reshape(B, nc, kc, H, hd).swapaxes(0, 1)
    Ft = F_g.reshape(B, nc, kc, H).swapaxes(0, 1)
    it = i_g.reshape(B, nc, kc, H).swapaxes(0, 1)
    t_pos = jnp.arange(T)
    pos_t = t_pos.reshape(nc, kc)

    def body(carry, xs):
        m_prev, num, den = carry          # m/den (B,H,T); num (B,H,T,hd)
        k_c, v_c, F_c, i_c, kp = xs
        # F_c/i_c: (B, kc, H) -> (B, H, 1, kc)
        expo = (F[..., :, None]
                - F_c.transpose(0, 2, 1)[..., None, :]
                + i_c.transpose(0, 2, 1)[..., None, :])      # (B,H,T,kc)
        mask = t_pos[:, None] >= kp[None, :]
        expo = jnp.where(mask[None, None], expo, -jnp.inf)
        m_new = jnp.maximum(jnp.maximum(m_prev, jnp.max(expo, axis=-1)),
                            -1e30)
        w = jnp.exp(expo - m_new[..., None])
        qk = jnp.einsum("bhtd,bshd->bhts", qf, k_c.astype(jnp.float32))
        sc = qk * w
        scale = jnp.exp(m_prev - m_new)
        num = num * scale[..., None] + jnp.einsum(
            "bhts,bshd->bhtd", sc, v_c.astype(jnp.float32))
        den = den * scale + jnp.sum(sc, axis=-1)
        return (m_new, num, den), ()

    m0 = jnp.full((B, H, T), -1e30, jnp.float32)
    num0 = jnp.zeros((B, H, T, hd), jnp.float32)
    den0 = jnp.zeros((B, H, T), jnp.float32)
    (m, num, den), _ = jax.lax.scan(body, (m0, num0, den0),
                                    (kt, vt, Ft, it, pos_t))
    y = num / jnp.maximum(jnp.abs(den), jnp.exp(-m))[..., None]
    y = y.swapaxes(1, 2).reshape(B, T, D).astype(x.dtype)
    y = rms_norm(y, lp["ln_sk"])
    return y @ lp["wo"]


def mlstm_cache_init(batch, d_model, n_heads, n_layers):
    hd = d_model // n_heads
    return {"C": jnp.zeros((n_layers, batch, n_heads, hd, hd), jnp.float32),
            "n": jnp.zeros((n_layers, batch, n_heads, hd), jnp.float32),
            "m": jnp.full((n_layers, batch, n_heads), -1e30, jnp.float32)}


def mlstm_decode_step(x, lp, C, n, m, *, n_heads: int):
    """O(1) recurrent step. x: (B, 1, D)."""
    B, _, D = x.shape
    H, hd = n_heads, D // n_heads
    qkv = x @ lp["wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, H, hd).astype(jnp.float32)
    k = (k.reshape(B, H, hd) / (hd ** 0.5)).astype(jnp.float32)
    v = v.reshape(B, H, hd).astype(jnp.float32)
    i_pre, log_f = _mlstm_gates(x, lp, H)
    i_pre, log_f = i_pre[:, 0], log_f[:, 0]           # (B, H)
    m_new = jnp.maximum(log_f + m, i_pre)
    dec = jnp.exp(log_f + m - m_new)[..., None]
    inp = jnp.exp(i_pre - m_new)[..., None]
    C = dec[..., None] * C + (inp * k)[..., :, None] * v[..., None, :]
    n = dec * n + inp * k
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)),
                      jnp.exp(-m_new))[..., None]
    y = (num / den).reshape(B, 1, D).astype(x.dtype)
    y = rms_norm(y, lp["ln_sk"])
    return y @ lp["wo"], C, n, m_new


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_init(key, d_model: int, n_heads: int, n_layers: int, dtype):
    ks = jax.random.split(key, 3)
    hd = d_model // n_heads
    return {
        "wx": _init(ks[0], (n_layers, d_model, 4 * d_model), dtype=dtype),
        # block-diagonal recurrent weights, one (hd, 4*hd) block per head
        "wr": _init(ks[1], (n_layers, n_heads, hd, 4 * hd), scale=hd ** -0.5,
                    dtype=jnp.float32),
        "b": jnp.zeros((n_layers, 4 * d_model), jnp.float32),
        "wo": _init(ks[2], (n_layers, d_model, d_model), dtype=dtype),
        "ln_sk": jnp.ones((n_layers, d_model), dtype),
    }


def _slstm_step(carry, xs, wr, n_heads):
    (h, c, n, m) = carry          # each (B, D) / m,n: (B, D)
    x_t = xs                      # (B, 4D) pre-activation from input
    B, D = h.shape
    hd = D // n_heads
    h_heads = h.reshape(B, n_heads, hd)
    rec = jnp.einsum("bkh,khf->bkf", h_heads, wr).reshape(B, 4 * D)
    z_pre, i_pre, f_pre, o_pre = jnp.split(x_t + rec, 4, axis=-1)
    z = jnp.tanh(z_pre)
    o = jax.nn.sigmoid(o_pre)
    log_f = -jax.nn.softplus(-f_pre)
    m_new = jnp.maximum(log_f + m, i_pre)
    i = jnp.exp(i_pre - m_new)
    f = jnp.exp(log_f + m - m_new)
    c = f * c + i * z
    n = jnp.maximum(f * n + i, jnp.exp(-m_new))
    h_new = o * (c / n)
    return (h_new, c, n, m_new), h_new


def slstm_block(x, lp, *, n_heads: int):
    """Chunked sequential sLSTM. x: (B, T, D).

    The recurrence is strictly sequential over T and couples all channels of
    a head — it cannot be sequence- or (16-way) channel-parallel. A/B
    measured: gathering x_pre once per layer (fp32, 1 GB) LOSES to letting
    the scan dynamic-slice-gather per chunk (54.5 vs 48 GB/dev total), so
    the per-chunk form is kept."""
    B, T, D = x.shape
    x_pre = (x @ lp["wx"]).astype(jnp.float32) + lp["b"]        # (B, T, 4D)
    zeros = jnp.zeros((B, D), jnp.float32)
    carry0 = (zeros, zeros, zeros + 1e-6, jnp.full((B, D), -1e30, jnp.float32))
    n_chunks = max(1, T // CHUNK)
    c = T // n_chunks
    xc = x_pre.reshape(B, n_chunks, c, 4 * D).swapaxes(0, 1).swapaxes(1, 2)

    step = partial(_slstm_step, wr=lp["wr"].astype(jnp.float32), n_heads=n_heads)

    @partial(jax.checkpoint, prevent_cse=False)
    def chunk(carry, x_chunk):
        return jax.lax.scan(step, carry, x_chunk)

    _, h = jax.lax.scan(chunk, carry0, xc)                      # (nc, c, B, D)
    h = h.reshape(T, B, D).swapaxes(0, 1).astype(x.dtype)
    h = rms_norm(h, lp["ln_sk"])
    return h @ lp["wo"]


def slstm_cache_init(batch, d_model, n_layers):
    z = jnp.zeros((n_layers, batch, d_model), jnp.float32)
    return {"h": z, "c": z, "n": z + 1e-6,
            "m": jnp.full((n_layers, batch, d_model), -1e30, jnp.float32)}


def slstm_decode_step(x, lp, h, c, n, m, *, n_heads: int):
    x_pre = (x[:, 0] @ lp["wx"]).astype(jnp.float32) + lp["b"]
    (h, c, n, m), h_out = _slstm_step((h, c, n, m), x_pre,
                                      lp["wr"].astype(jnp.float32), n_heads)
    y = rms_norm(h_out[:, None, :].astype(x.dtype), lp["ln_sk"])
    return y @ lp["wo"], h, c, n, m


# ---------------------------------------------------------------------------
# full xLSTM LM: super-blocks of 4 (3 mLSTM + 1 sLSTM), scanned over depth.
# d_ff == 0 in the assigned config: blocks carry their own projections.
# ---------------------------------------------------------------------------

GROUP = 4  # 3 mLSTM + 1 sLSTM per super-block


def init_params(key, cfg):
    ng = cfg.n_layers // GROUP
    D, V, H, dtype = cfg.d_model, cfg.vocab, cfg.n_heads, cfg.dtype
    ks = jax.random.split(key, 5)
    p = {
        "embed": _init(ks[0], (V, D), scale=0.02, dtype=dtype),
        "mlstm": mlstm_init(ks[1], D, H, ng * (GROUP - 1), dtype),
        "slstm": slstm_init(ks[2], D, H, ng, dtype),
        "ln": jnp.ones((ng, GROUP, D), dtype),
        "lnf": jnp.ones((D,), dtype),
    }
    p["mlstm"] = jax.tree.map(lambda w: w.reshape(ng, GROUP - 1, *w.shape[1:]),
                              p["mlstm"])
    if not cfg.tie_embeddings:
        p["lm_head"] = _init(ks[3], (D, V), scale=0.02, dtype=dtype)
    return p


def _group_fwd(cfg, x, gp):
    for s in range(GROUP):
        xn = rms_norm(x, gp["ln"][s])
        if s < GROUP - 1:
            lp = jax.tree.map(lambda w: w[s], gp["mlstm"])
            x = x + mlstm_block(xn, lp, n_heads=cfg.n_heads)
        else:
            x = x + slstm_block(xn, gp["slstm"], n_heads=cfg.n_heads)
    return x


def forward_hidden(params, tokens, cfg):
    x = params["embed"][tokens]
    stack = {k: params[k] for k in ("mlstm", "slstm", "ln")}

    @partial(jax.checkpoint, prevent_cse=False)
    def body(x, gp):
        return _group_fwd(cfg, x, gp), ()

    x, _ = jax.lax.scan(body, x, stack)
    return rms_norm(x, params["lnf"])


def _head(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, tokens, cfg):
    return (forward_hidden(params, tokens, cfg) @ _head(params, cfg)
            ).astype(jnp.float32)


def loss_fn(params, batch, cfg):
    from repro.models.layers import chunked_ce
    x = forward_hidden(params, batch["tokens"], cfg)
    return chunked_ce(x[:, :-1], _head(params, cfg), batch["tokens"][:, 1:],
                      chunk=cfg.q_chunk)


def init_cache(cfg, batch_size: int, max_len: int):
    del max_len  # recurrent: O(1) state
    ng = cfg.n_layers // GROUP
    mc = mlstm_cache_init(batch_size, cfg.d_model, cfg.n_heads, ng * (GROUP - 1))
    sc = slstm_cache_init(batch_size, cfg.d_model, ng)
    mc = jax.tree.map(lambda w: w.reshape(ng, GROUP - 1, *w.shape[1:]), mc)
    return {"m": mc, "s": sc}


def decode_step(params, cache, tokens, position, cfg):
    del position
    x = params["embed"][tokens]
    stack = {k: params[k] for k in ("mlstm", "slstm", "ln")}

    def body(x, scanned):
        gp, mC, mn, mm, sh, sc_, sn, sm = scanned
        new_m = {"C": [], "n": [], "m": []}
        for s in range(GROUP):
            xn = rms_norm(x, gp["ln"][s])
            if s < GROUP - 1:
                lp = jax.tree.map(lambda w: w[s], gp["mlstm"])
                y, C, n, m = mlstm_decode_step(xn, lp, mC[s], mn[s], mm[s],
                                               n_heads=cfg.n_heads)
                new_m["C"].append(C); new_m["n"].append(n); new_m["m"].append(m)
                x = x + y
            else:
                y, sh, sc_, sn, sm = slstm_decode_step(xn, gp["slstm"],
                                                       sh, sc_, sn, sm,
                                                       n_heads=cfg.n_heads)
                x = x + y
        return x, (jnp.stack(new_m["C"]), jnp.stack(new_m["n"]),
                   jnp.stack(new_m["m"]), sh, sc_, sn, sm)

    x, (C, n, m, sh, sc_, sn, sm) = jax.lax.scan(
        body, x, (stack, cache["m"]["C"], cache["m"]["n"], cache["m"]["m"],
                  cache["s"]["h"], cache["s"]["c"], cache["s"]["n"],
                  cache["s"]["m"]))
    x = rms_norm(x, params["lnf"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    new_cache = {"m": {"C": C, "n": n, "m": m},
                 "s": {"h": sh, "c": sc_, "n": sn, "m": sm}}
    return (x @ head).astype(jnp.float32), new_cache
