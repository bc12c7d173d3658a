"""Shared layers: RMSNorm, RoPE, GQA attention (full / sliding-window,
train + KV-cache decode), latent attention (MLA, train + latent-cache
decode), SwiGLU MLP, sort-free capacity MoE, and dropless routed experts of
which a chip holds a share.

All layer parameter trees are built *stacked over depth* (leading dim L) so
model forwards are a single ``lax.scan`` over layers — compile time and HLO
size independent of depth.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.core.spans import phase


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / (shape[-2] ** 0.5 if len(shape) >= 2 else 1.0)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def rope_freqs(d_head: int, theta: float = 1e4):
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, jnp.float32) / d_head))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qkv_bias: bool = False
    sliding_window: int = 0   # 0 => full causal
    rope_theta: float = 1e4
    q_chunk: int = 512        # query-chunked softmax (VMEM-friendly)
    causal: bool = True       # False => bidirectional (encoders)


def attn_init(key, cfg: AttnCfg, n_layers: int, dtype):
    ks = jax.random.split(key, 4)
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p = {
        "wq": _init(ks[0], (n_layers, D, H * hd), dtype=dtype),
        "wk": _init(ks[1], (n_layers, D, K * hd), dtype=dtype),
        "wv": _init(ks[2], (n_layers, D, K * hd), dtype=dtype),
        "wo": _init(ks[3], (n_layers, H * hd, D), dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_layers, H * hd), dtype)
        p["bk"] = jnp.zeros((n_layers, K * hd), dtype)
        p["bv"] = jnp.zeros((n_layers, K * hd), dtype)
    return p


def _qkv(x, lp, cfg: AttnCfg, positions):
    B, S, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ lp["wq"]
    k = x @ lp["wk"]
    v = x @ lp["wv"]
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if S > 1:
        # the barrier keeps K/V in the stored dtype (XLA otherwise fuses the
        # fp32 upcast of the scores matmul into them); the name lets the
        # layer remat policies SAVE K/V rather than recompute the
        # projections and RoPE in the backward pass
        k, v = jax.lax.optimization_barrier((k, v))
        k = jax.ad_checkpoint.checkpoint_name(k, "attn_kv")
        v = jax.ad_checkpoint.checkpoint_name(v, "attn_kv")
    return q, k, v


def _sdpa_chunk(q_chunk, k, v, q_pos, k_pos, cfg: AttnCfg):
    """softmax(q k^T) v for one query chunk against full K/V.

    q_chunk: (B, c, H, hd); k/v: (B, S, K, hd). GQA: repeat kv groups.
    """
    B, c, H, hd = q_chunk.shape
    S, K = k.shape[1], k.shape[2]
    rep = H // K
    # grouped-GQA einsum instead of jnp.repeat: keeps the K(=kv) head dim
    # explicit so backward reduces dK/dV at kv-head width.
    q5 = q_chunk.reshape(B, c, K, rep, hd)
    scores = jnp.einsum("bcgrd,bsgd->bgrcs", q5, k,
                        preferred_element_type=jnp.float32) / (hd ** 0.5)
    if cfg.causal:
        mask = q_pos[:, None] >= k_pos[None, :]                   # (c, S)
        if cfg.sliding_window > 0:
            mask &= (q_pos[:, None] - k_pos[None, :]) < cfg.sliding_window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_chunk.dtype)
    out = jnp.einsum("bgrcs,bsgd->bcgrd", probs, v)
    return out.reshape(B, c, H, v.shape[-1])


def _flash_kv_attention(q, k, v, positions, cfg: AttnCfg, kv_chunk: int,
                        remat: bool = False):
    """Flash-style attention chunked over the KEY/VALUE axis with online
    softmax; q/k: (B, S, H|K, hd), v: (B, S, K, vd), vd the V width (latent
    attention's differs from the QK width). Chunking the keys drops peak
    scores memory from (B,H,S,S) to (B,H,S,kc). ``remat`` recomputes each chunk's scores in the
    backward pass, which then keeps only the carry of each chunk and not its
    (B,H,S,kc) probabilities: at S = 8192 those come to 4 GB a chunk stack.
    """
    B, S, H, hd = q.shape
    K, vd = k.shape[2], v.shape[-1]
    rep = H // K
    kc = min(kv_chunk, S)
    if S % kc != 0:
        kc = S
    nc = S // kc
    q5 = q.reshape(B, S, K, rep, hd)
    kt = k.reshape(B, nc, kc, K, hd).swapaxes(0, 1)
    vt = v.reshape(B, nc, kc, K, vd).swapaxes(0, 1)
    pos_t = positions.reshape(nc, kc)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_c, v_c, kp = xs
        s = jnp.einsum("bsgrd,btgd->bgrst", q5, k_c,
                       preferred_element_type=jnp.float32) / (hd ** 0.5)
        if cfg.causal:
            mask = positions[:, None] >= kp[None, :]
            if cfg.sliding_window > 0:
                mask &= (positions[:, None] - kp[None, :]) < cfg.sliding_window
            s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        scale = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * scale + jnp.sum(p, axis=-1)
        acc = acc * scale[..., None] + jnp.einsum(
            "bgrst,btgd->bgrsd", p.astype(v_c.dtype), v_c,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), ()

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    m0 = jnp.full((B, K, rep, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, rep, S), jnp.float32)
    acc0 = jnp.zeros((B, K, rep, S, vd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kt, vt, pos_t))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # (B, K, rep, S, vd) -> (B, S, H * vd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H * vd).astype(q.dtype)


@phase("model.attn")
def attention(x, lp, cfg: AttnCfg, positions):
    """Training attention. x: (B, S, D) -> (B, S, D).

    Single-block SDPA for small S (tests / reduced configs); flash-style
    KV-chunked online softmax for long sequences.
    """
    B, S, D = x.shape
    q, k, v = _qkv(x, lp, cfg, positions)
    if S <= cfg.q_chunk:
        y = _sdpa_chunk(q, k, v, positions, positions, cfg)
        y = y.reshape(B, S, cfg.n_heads * cfg.d_head)
    else:
        y = _flash_kv_attention(q, k, v, positions, cfg, cfg.q_chunk)
    return y @ lp["wo"]


def attention_decode(x, lp, cfg: AttnCfg, cache_k, cache_v, position):
    """One-token decode with a pre-filled KV cache.

    x: (B, 1, D); cache_k/v: (B, S_cache, K, hd); position: scalar int32 index
    where the new token's K/V is written.  Returns (y, new_k, new_v).
    """
    B = x.shape[0]
    pos_arr = jnp.full((B, 1), position, jnp.int32)
    q, k_new, v_new = _qkv(x, lp, cfg, pos_arr)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), position, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), position, axis=1)
    S = cache_k.shape[1]
    k_pos = jnp.arange(S, dtype=jnp.int32)
    q_pos = jnp.full((1,), position, jnp.int32)
    valid = k_pos <= position
    if cfg.sliding_window > 0:
        valid &= (position - k_pos) < cfg.sliding_window
    K, rep = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    q5 = q.reshape(B, 1, K, rep, cfg.d_head)
    scores = jnp.einsum("bcgrd,bsgd->bgrcs", q5, cache_k,
                        preferred_element_type=jnp.float32) / (cfg.d_head ** 0.5)
    scores = jnp.where(valid[None, None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    y = jnp.einsum("bgrcs,bsgd->bcgrd", probs, cache_v).reshape(B, 1, -1)
    return y @ lp["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2 arXiv:2405.04434 §2.1, no query LoRA)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLACfg:
    d_model: int
    n_heads: int
    kv_lora_rank: int         # width of the latent c_kv
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float = 1e4
    q_chunk: int = 512
    causal: bool = True
    sliding_window: int = 0

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim


def mla_init(key, cfg: MLACfg, n_layers: int, dtype):
    ks = jax.random.split(key, 4)
    D, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "wq": _init(ks[0], (n_layers, D, H * cfg.qk_dim), dtype=dtype),
        "wkv_a": _init(ks[1], (n_layers, D, r + cfg.qk_rope_dim),
                       dtype=dtype),
        "ln_kv": jnp.ones((n_layers, r), dtype),
        "wkv_b": _init(ks[2], (n_layers, r,
                               H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                       dtype=dtype),
        "wo": _init(ks[3], (n_layers, H * cfg.v_head_dim, D), dtype=dtype),
    }


def _mla_q(x, lp, cfg: MLACfg, positions):
    """(B, S, H, qk_dim): per head [q_nope | RoPE(q_pe)]."""
    B, S, _ = x.shape
    q = (x @ lp["wq"]).reshape(B, S, cfg.n_heads, cfg.qk_dim)
    q_pe = apply_rope(q[..., cfg.qk_nope_dim:], positions, cfg.rope_theta)
    return jnp.concatenate([q[..., :cfg.qk_nope_dim], q_pe], axis=-1)


def _mla_latent(x, lp, cfg: MLACfg, positions):
    """-> (RMSNorm(c_kv) (B, S, r), RoPE(k_pe) (B, S, qk_rope_dim)): what a
    position leaves in the latent cache. The latent's RMSNorm runs the
    default eps, as DeepSeek's ``kv_a_layernorm`` does whatever the model's
    ``rms_norm_eps``."""
    kv = x @ lp["wkv_a"]
    r = cfg.kv_lora_rank
    c_kv = rms_norm(kv[..., :r], lp["ln_kv"])
    k_pe = apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    return c_kv, k_pe[..., 0, :]


def _mla_kv(c_kv, k_pe, lp, cfg: MLACfg):
    """Latent -> per-head keys [k_nope | k_pe] (k_pe shared by all heads)
    and values, (B, S, H, qk_dim) and (B, S, H, v_head_dim)."""
    B, S, _ = c_kv.shape
    H, nope = cfg.n_heads, cfg.qk_nope_dim
    kv = (c_kv @ lp["wkv_b"]).reshape(B, S, H, nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, cfg.qk_rope_dim))
    return jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]


@phase("model.attn")
def mla_attention(x, lp, cfg: MLACfg, positions):
    """Training latent attention. x: (B, S, D) -> (B, S, D). Scores are
    (q_nope.k_nope + q_pe.k_pe) / sqrt(qk_dim), through the same cores as
    ``attention`` with one K/V head per query head."""
    B, S, _ = x.shape
    q = _mla_q(x, lp, cfg, positions)
    k, v = _mla_kv(*_mla_latent(x, lp, cfg, positions), lp, cfg)
    if S <= cfg.q_chunk:
        y = _sdpa_chunk(q, k, v, positions, positions, cfg).reshape(B, S, -1)
    else:
        y = _flash_kv_attention(q, k, v, positions, cfg, cfg.q_chunk,
                                remat=True)
    return y @ lp["wo"]


def mla_decode(x, lp, cfg: MLACfg, cache_c, cache_pe, position):
    """One-token decode through the latent cache: c_kv (B, S_cache, r) and
    k_pe (B, S_cache, qk_rope_dim) per position; keys and values are
    expanded from it. Returns (y, new cache_c, new cache_pe)."""
    B = x.shape[0]
    pos = jnp.full((B, 1), position, jnp.int32)
    q = _mla_q(x, lp, cfg, pos)
    c_new, pe_new = _mla_latent(x, lp, cfg, pos)
    cache_c = jax.lax.dynamic_update_slice_in_dim(
        cache_c, c_new.astype(cache_c.dtype), position, axis=1)
    cache_pe = jax.lax.dynamic_update_slice_in_dim(
        cache_pe, pe_new.astype(cache_pe.dtype), position, axis=1)
    k, v = _mla_kv(cache_c, cache_pe, lp, cfg)
    valid = jnp.arange(cache_c.shape[1]) <= position
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (cfg.qk_dim ** 0.5)
    scores = jnp.where(valid[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    y = jnp.einsum("bhqs,bshd->bqhd", probs, v).reshape(B, 1, -1)
    return y @ lp["wo"], cache_c, cache_pe


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp_init(key, d_model, d_ff, n_layers, dtype):
    ks = jax.random.split(key, 3)
    return {"w1": _init(ks[0], (n_layers, d_model, d_ff), dtype=dtype),
            "w3": _init(ks[1], (n_layers, d_model, d_ff), dtype=dtype),
            "w2": _init(ks[2], (n_layers, d_ff, d_model), dtype=dtype)}


def swiglu(x, lp):
    return (jax.nn.silu(x @ lp["w1"]) * (x @ lp["w3"])) @ lp["w2"]


def chunked_ce(x, head, targets, mask=None, chunk: int = 512):
    """Sequence-chunked cross entropy: never materializes (B, S, V) logits.

    x: (B, S, D) final hidden (caller drops the last position);
    head: (D, V); targets: (B, S) int32; mask: (B, S) float or None.
    The per-chunk body is rematerialized, so backward also stays at
    (B, chunk, V) peak.
    """
    B, S, D = x.shape
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = (S + pad) // c
    xc = x.reshape(B, nc, c, D).swapaxes(0, 1)
    tc = targets.reshape(B, nc, c).swapaxes(0, 1)
    mc = mask.reshape(B, nc, c).swapaxes(0, 1)

    @partial(jax.checkpoint, prevent_cse=False)
    def body(carry, xs):
        xb, tb, mb = xs
        logits = (xb @ head).astype(jnp.float32)
        # one-hot contraction instead of take_along_axis: the reduction over
        # the (vocab-sharded) axis stays local + a tiny all-reduce, instead of
        # an all-gather of the full (B, chunk, V) logits.
        lse = jax.nn.logsumexp(logits, axis=-1)
        oh = jax.nn.one_hot(tb, logits.shape[-1], dtype=logits.dtype)
        tgt = jnp.einsum("bcv,bcv->bc", logits, oh)
        nll = lse - tgt
        return (carry[0] + jnp.sum(nll * mb), carry[1] + jnp.sum(mb)), ()

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (xc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def moe_init(key, d_model, d_ff, n_experts, n_layers, dtype):
    ks = jax.random.split(key, 4)
    return {"router": _init(ks[0], (n_layers, d_model, n_experts), dtype=jnp.float32),
            "w1": _init(ks[1], (n_layers, n_experts, d_model, d_ff), dtype=dtype),
            "w3": _init(ks[2], (n_layers, n_experts, d_model, d_ff), dtype=dtype),
            "w2": _init(ks[3], (n_layers, n_experts, d_ff, d_model), dtype=dtype)}


def _topk_iterative(scores, k: int):
    """top-k via k argmax+mask rounds."""
    vals, idxs = [], []
    s = scores
    for _ in range(k):
        i = jnp.argmax(s, axis=-1)
        v = jnp.max(s, axis=-1)
        vals.append(v)
        idxs.append(i)
        s = s - jax.nn.one_hot(i, scores.shape[-1], dtype=s.dtype) * 1e9
    return jnp.stack(vals, axis=-1), jnp.stack(idxs, axis=-1)


def moe_apply(x, lp, n_experts: int, top_k: int, capacity_factor: float = 1.25,
              ep: bool = False):
    """Capacity-based top-k MoE over every expert, dispatched per sequence:
    each expert takes at most C = S * k / E * capacity_factor tokens of a
    sequence and the overflow is dropped.

    * ep=False: dispatch and combine are a scatter into and a gather out of
      the (E, C, D) expert buffer.
    * ep=True: both are one-hot einsums; at d_ff >= 8k the extra
      (E*C)/(3*d_ff) ~ 1% FLOPs is noise.
    """
    B, S, D = x.shape
    E, k = n_experts, top_k
    C = max(1, int(S * k / E * capacity_factor))

    logits = x.astype(jnp.float32) @ lp["router"]            # (B, S, E)
    gate_all = jax.nn.softmax(logits, axis=-1)
    gates, idx = _topk_iterative(gate_all, k)                # (B, S, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(B, S * k)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # (B, S*k, E)
    pos = jnp.cumsum(oh, axis=1) - oh
    pos = jnp.sum(pos * oh, axis=-1)                         # (B, S*k)
    keep = pos < C
    e_idx = jnp.where(keep, flat_e, E - 1)
    p_idx = jnp.where(keep, pos, C - 1)
    # The slot->cell map is INJECTIVE on kept slots, so scatter and gather
    # are exact transposes of each other. XLA cannot know this: the autodiff
    # transpose of the batched gather is a general scatter. custom_vjp
    # encodes the injectivity — dispatch^T = collect, collect^T = dispatch.

    def _scatter(vals, el, pl):
        f = lambda v, e, p: jnp.zeros((E, C, D), v.dtype).at[e, p].add(v)
        return jax.vmap(f)(vals, el, pl)

    def _collect(buf, el, pl):
        return jax.vmap(lambda b1, e, p: b1[e, p])(buf, el, pl)

    @jax.custom_vjp
    def moe_dispatch(vals, el, pl):
        return _scatter(vals, el, pl)

    moe_dispatch.defvjp(
        lambda vals, el, pl: (_scatter(vals, el, pl), (el, pl)),
        lambda res, d_buf: (_collect(d_buf, *res), None, None))

    @jax.custom_vjp
    def moe_collect(buf, el, pl):
        return _collect(buf, el, pl)

    moe_collect.defvjp(
        lambda buf, el, pl: (_collect(buf, el, pl), (el, pl)),
        lambda res, d_out: (_scatter(d_out, *res), None, None))

    vals = jnp.broadcast_to(x[:, :, None, :], (B, S, k, D)).reshape(
        B, S * k, D)
    vals = jnp.where(keep[..., None], vals, 0).astype(x.dtype)

    if ep:
        cell = jnp.where(keep, e_idx * C + p_idx, E * C)
        oh = jax.nn.one_hot(cell, E * C, dtype=x.dtype)      # (B, S*k, EC)
        buf = jnp.einsum("bsk,bsd->bkd", oh, vals).reshape(B, E, C, D)
    else:
        buf = moe_dispatch(vals, e_idx, p_idx)               # (B, E, C, D)

    h = jnp.einsum("becd,edf->becf", buf, lp["w1"])
    g3 = jnp.einsum("becd,edf->becf", buf, lp["w3"])
    y = jnp.einsum("becf,efd->becd", jax.nn.silu(h) * g3, lp["w2"])

    if ep:
        out_slots = jnp.einsum("bsk,bkd->bsd", oh,
                               y.reshape(B, E * C, D).astype(x.dtype))
    else:
        out_slots = moe_collect(y.astype(x.dtype), e_idx, p_idx)
    out_slots = jnp.where(keep[..., None], out_slots, 0) \
        * gates.reshape(B, S * k)[..., None].astype(x.dtype)
    out = out_slots.reshape(B, S, k, D).sum(axis=2)
    frac = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                    axis=(0, 1))
    prob = jnp.mean(gate_all, axis=(0, 1))
    aux = E * jnp.sum(frac * prob)
    return out, aux


# ---------------------------------------------------------------------------
# dropless routed experts, of which this chip holds a contiguous share
# (DeepSeek-V3 arXiv:2412.19437 §2.1.2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExpertCfg:
    d_model: int
    d_ff: int                 # width of one routed expert
    n_experts: int            # the router's outputs: every expert
    top_k: int
    n_shared: int             # shared experts, one SwiGLU of n_shared * d_ff
    held: int                 # experts this chip holds ...
    held_start: int = 0       # ... from this one on
    scale: float = 1.0        # routed_scaling_factor


#: grouped-matmul tiles (rows, contraction, output columns), cut to the
#: problem where it is smaller
GMM_TILES = (512, 1024, 1024)


def experts_init(key, cfg: ExpertCfg, n_layers: int, dtype):
    ks = jax.random.split(key, 5)
    D, F, Eh = cfg.d_model, cfg.d_ff, cfg.held
    return {"router": _init(ks[0], (n_layers, D, cfg.n_experts), dtype=dtype),
            "experts": {
                "w1": _init(ks[1], (n_layers, Eh, D, F), dtype=dtype),
                "w3": _init(ks[2], (n_layers, Eh, D, F), dtype=dtype),
                "w2": _init(ks[3], (n_layers, Eh, F, D), dtype=dtype)},
            "shared": mlp_init(ks[4], D, cfg.n_shared * F, n_layers, dtype)}


@phase("model.moe.route")
def route(x, router, cfg: ExpertCfg):
    """x: (B, S, D) -> (weights (B, S, k) f32, experts (B, S, k) int32,
    sequence-wise balance loss). Sigmoid scores over all ``n_experts`` in
    f32; the top-k's scores normalised to sum 1, times ``scale``."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    top, idx = jax.lax.top_k(scores, cfg.top_k)
    w = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) * cfg.scale
    return w, idx, balance_loss(scores, idx, cfg.n_experts)


def balance_loss(scores, idx, n_experts: int):
    """DeepSeek-V3's sequence-wise balance loss sum_i f_i P_i, averaged over
    the batch: f_i = E / (k S) * (tokens of the sequence that select i),
    P_i the mean over the sequence of the scores normalised over all E."""
    S, k = idx.shape[1], idx.shape[2]
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    f = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32),
                axis=(1, 2)) * (n_experts / (k * S))
    return jnp.mean(jnp.sum(f * jnp.mean(share, axis=1), axis=-1))


def grouped_matmul(lhs, rhs, sizes, start):
    """Rows of ``lhs`` (m, K), sorted by expert, times their expert's matrix:
    ``rhs`` (held, K, N) holds experts start .. start + held - 1 of the
    ``sizes`` (n_experts,) groups. Rows of the other experts come out zero;
    work is done for the held experts' rows alone (megablox ``gmm``; its
    kernels carry the names of its jitted entry points, ``gmm`` and, for the
    weight gradient, ``tgmm``, inside those of the transforms that reach
    them, e.g. ``transpose_jvp_jit_gmm___``)."""
    # imported here, as the kernels' modules are: importing Pallas takes
    # over a second, which a model without an expert layer need not wait for
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from repro.kernels.common import interpret_mode
    tiles = tuple(min(t, n) for t, n in
                  zip(GMM_TILES, (lhs.shape[0],) + rhs.shape[1:]))
    return megablox.gmm(lhs, rhs, sizes, lhs.dtype, tiles,
                        jnp.asarray(start, jnp.int32), None, False,
                        interpret_mode())


@phase("model.moe.route")
def sort_by_expert(idx, n_experts: int):
    """idx: (T, k) -> (the (token, slot) pairs in the order of their
    expert, (T k,) int32; rows per expert, (n_experts,) int32)."""
    flat = idx.reshape(-1)
    return (jnp.argsort(flat, stable=True).astype(jnp.int32),
            jnp.bincount(flat, length=n_experts).astype(jnp.int32))


@phase("model.moe.experts")
def held_experts(x, w, order, sizes, lp, cfg: ExpertCfg):
    """Dropless: every (token, expert) pair routed to a held expert is
    computed, however the routing is skewed. x: (T, D); w: (T, k); order,
    sizes: ``sort_by_expert``'s. -> (T, D), the held experts' part of the
    routed sum."""
    T, D = x.shape
    m = T * cfg.top_k
    tm = min(GMM_TILES[0], -(-m // 8) * 8)
    xs = jnp.pad(x[order // cfg.top_k], ((0, -m % tm), (0, 0)))
    e = lp["experts"]
    h = grouped_matmul(xs, e["w1"], sizes, cfg.held_start)
    g = grouped_matmul(xs, e["w3"], sizes, cfg.held_start)
    y = grouped_matmul(jax.nn.silu(h) * g, e["w2"], sizes, cfg.held_start)
    # back to (token, slot) order; padding rows are never read
    back = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    y = y[back].reshape(T, cfg.top_k, D)
    return jnp.einsum("tkd,tk->td", y.astype(jnp.float32), w).astype(x.dtype)


@phase("model.moe.shared")
def shared_experts(x, lp):
    return swiglu(x, lp["shared"])


def routed_experts(x, lp, cfg: ExpertCfg):
    """shared(x) + sum over the selected experts this chip holds of
    w_i expert_i(x), with the routing over all experts and its balance
    loss. No code stands in for the experts held elsewhere or for the
    exchange with them. x: (B, S, D) -> ((B, S, D), balance loss)."""
    B, S, D = x.shape
    w, idx, aux = route(x, lp["router"], cfg)
    order, sizes = sort_by_expert(idx, cfg.n_experts)
    y = held_experts(x.reshape(B * S, D), w.reshape(B * S, -1), order,
                     sizes, lp, cfg).reshape(B, S, D)
    return y + shared_experts(x, lp), aux
