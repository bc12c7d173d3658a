"""The mla_moe model family (DeepSeek-V3 layout; Moonlight-16B-A3B): a plain
float32 model of latent attention and routed experts of which the chip
holds a share (its loss and gradients), the check that a configuration
describes the program's model, and the training FLOPs one token costs on
this chip.

The family contract (``loss``, ``check``, ``flops_per_token``) is
``dense.py``'s; this module adds ``expert_flops_per_token``, what the held
experts' grouped matmuls compute a token, for ``expert_roofline``.

The architecture, as DeepSeek-V2 (arXiv:2405.04434 §2.1) and DeepSeek-V3
(arXiv:2412.19437 §2.1.2) and the model's ``config.json`` describe it, with
no query LoRA (``q_lora_rank`` null). Per layer, pre-RMSNorm:

- latent attention: ``q = h W_q`` gives per head ``q_nope`` and ``q_pe``;
  ``[c_kv | k_pe] = h W_kv_a``; ``c_kv`` goes through an RMSNorm (eps
  ``kv_lora_norm_eps``) and ``c_kv W_kv_b`` gives per head ``k_nope`` and
  ``v``; RoPE (base ``rope_theta``) on ``q_pe`` and on ``k_pe``, which all
  heads share; scores ``(q_nope.k_nope + q_pe.k_pe) / sqrt(qk_nope + qk_rope)``
  under the causal mask; ``softmax . v`` through ``W_o``;
- the first ``first_k_dense_replace`` layers: a SwiGLU FFN of
  ``intermediate_size``;
- the others: router scores ``s = sigmoid(h W_r)`` over all the published
  experts (``published.n_routed_experts``), the top ``num_experts_per_tok``
  of ``s`` (``e_score_correction_bias`` held at zero, one group), weights
  the selected scores normalised to sum 1 times ``routed_scaling_factor``;
  the output is ``shared(h) + sum over the held experts i of g_i
  expert_i(h)``, ``g_i`` the weight where i is selected and 0 elsewhere,
  the held experts being ``held_expert_start`` on, ``n_routed_experts`` of
  them; ``shared`` one SwiGLU of ``n_shared_experts * moe_intermediate_size``;
  each held expert runs densely over every token, times its gate;
- the sequence-wise balance loss over all the published experts,
  ``sum_i f_i P_i`` with ``f_i = E / (k S)`` times the count of the
  sequence's tokens that select i and ``P_i`` the sequence's mean of
  ``s_i / sum_j s_j``, averaged over the batch, summed over the expert
  layers, times ``aux_loss_alpha``;
- a final RMSNorm, the untied head over the vocabulary slice, and the mean
  next-token cross entropy.

Every value is float32 and every contraction runs at HIGHEST precision.
Attention and the cross entropy run in query blocks, rematerialised, so
that an 8,192-long sequence fits. ``quantize`` rounds the inputs of every
contraction and the residual stream after each addition, as in
``dense.py``. Parameters come in the program's layout: ``embed``,
``lm_head``, ``lnf``, and the stacks ``dense`` and ``moe``, each stacked
over depth (``attn``: ``wq``, ``wkv_a``, ``ln_kv``, ``wkv_b``, ``wo``;
``ln1``, ``ln2``; ``mlp`` or ``router``, ``experts``, ``shared``: ``w1``,
``w3``, ``w2``).

One departure from the published model: RoPE is the rotate-half form. The
published code rotates interleaved pairs of the rope columns, a fixed
permutation of the rope columns of ``W_q`` and ``W_kv_a``: with random
weights the two are the same model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.dense import _mm, identity, rms_norm, rope

#: query rows a block of attention or of the cross entropy holds
BLOCK = 512

#: configuration-file key -> the program's ModelCfg field it must equal
MODEL_FIELDS = {
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "n_dense_layers",
    "num_experts_per_tok": "moe_topk",
    "n_shared_experts": "moe_shared",
    "n_routed_experts": "moe_held",
    "held_expert_start": "moe_held_start",
    "rope_theta": "rope_theta",
}
#: keys whose value is the only one the program computes
FIXED = {"q_lora_rank": None, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "norm_topk_prob": True, "seq_aux": True,
         "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
         "num_nextn_predict_layers": 0}
#: keys compared to a float field within rounding
CLOSE = {"routed_scaling_factor": "moe_scale", "aux_loss_alpha":
         "moe_aux_alpha", "rms_norm_eps": "rms_eps"}


def _attention(cfg, h, a, quantize):
    B, S, _ = h.shape
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, pe, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    theta = cfg["rope_theta"]
    q = _mm("bsd,de->bse", h, a["wq"], quantize).reshape(B, S, H, nope + pe)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    kv = _mm("bsd,de->bse", h, a["wkv_a"], quantize)
    c = rms_norm(kv[..., :r], a["ln_kv"], cfg["kv_lora_norm_eps"])
    k_pe = rope(kv[..., None, r:], theta)
    kv = _mm("bsr,re->bse", c, a["wkv_b"], quantize).reshape(
        B, S, H, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, pe))], -1)
    v = kv[..., nope:]
    c_q = BLOCK if S % BLOCK == 0 else S

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = _mm("bqhd,bkhd->bhqk", qb, k, quantize) / math.sqrt(nope + pe)
        rows = start + jnp.arange(c_q)
        s = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   quantize)

    qb = q.reshape(B, S // c_q, c_q, H, nope + pe).swapaxes(0, 1)
    o = jax.lax.map(block, (qb, jnp.arange(0, S, c_q)))
    o = o.swapaxes(0, 1).reshape(B, S, H * vd)
    return _mm("bse,ed->bsd", o, a["wo"], quantize)


def _swiglu(h, m, quantize):
    gate = _mm("bsd,df->bsf", h, m["w1"], quantize)
    up = _mm("bsd,df->bsf", h, m["w3"], quantize)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w2"], quantize)


def _experts(cfg, h, lp, quantize):
    """-> (shared + the held experts' gated sum, balance loss)."""
    k = cfg["num_experts_per_tok"]
    start, held = cfg["held_expert_start"], cfg["n_routed_experts"]
    s = jax.nn.sigmoid(_mm("bsd,de->bse", h, lp["router"], quantize))
    E = s.shape[-1]
    top, idx = jax.lax.top_k(s, k)
    w = top / (jnp.sum(top, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    chosen = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (B, S, k, E)
    gate = jnp.einsum("bske,bsk->bse", chosen, w,
                      precision=jax.lax.Precision.HIGHEST)
    e = lp["experts"]
    a1 = _mm("bsd,edf->bsef", h, e["w1"], quantize)
    a3 = _mm("bsd,edf->bsef", h, e["w3"], quantize)
    u = jax.nn.silu(a1) * a3 * gate[..., start:start + held, None]
    y = _mm("bsef,efd->bsd", u, e["w2"], quantize) \
        + _swiglu(h, lp["shared"], quantize)
    S = s.shape[1]
    f = jnp.sum(chosen, axis=(1, 2)) * (E / (k * S))
    P = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=1)
    return y, jnp.mean(jnp.sum(f * P, -1))


def block(cfg, kind, x, lp, quantize):
    """One layer of the ``dense`` or ``moe`` stack. x: (B, S, D) f32."""
    eps = cfg["rms_norm_eps"]
    x = quantize(x + _attention(cfg, rms_norm(x, lp["ln1"], eps),
                                lp["attn"], quantize))
    h = rms_norm(x, lp["ln2"], eps)
    if kind == "dense":
        return quantize(x + _swiglu(h, lp["mlp"], quantize)), 0.0
    y, aux = _experts(cfg, h, lp, quantize)
    return quantize(x + y), aux


def _cross_entropy(x, head, targets, quantize):
    """Mean NLL of targets (B, T) under logits x head, in blocks of rows."""
    B, T, D = x.shape
    pad = -T % BLOCK
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    t = jnp.pad(targets, ((0, 0), (0, pad)))
    keep = jnp.pad(jnp.ones((B, T), jnp.float32), ((0, 0), (0, pad)))
    n = (T + pad) // BLOCK

    @jax.checkpoint
    def nll(args):
        xb, tb, kb = args
        logp = jax.nn.log_softmax(_mm("bsd,dv->bsv", xb, head, quantize), -1)
        return -jnp.sum(jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]
                        * kb)

    split = lambda a: a.reshape((B, n, BLOCK) + a.shape[2:]).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(nll, (split(x), split(t), split(keep)))) \
        / (B * T)


def loss(cfg, params, tokens, quantize=identity):
    """Mean next-token cross entropy of ``tokens`` (B, S) int32 under f32
    ``params``, plus the weighted balance loss."""
    x = quantize(params["embed"][tokens])
    aux = jnp.zeros((), jnp.float32)
    for kind in ("dense", "moe"):
        if kind not in params:
            continue

        @jax.checkpoint
        def layer(carry, lp, kind=kind):
            x, a = block(cfg, kind, carry[0], lp, quantize)
            return (x, carry[1] + a), None

        (x, aux), _ = jax.lax.scan(layer, (x, aux), params[kind])
    x = rms_norm(x, params["lnf"], cfg["rms_norm_eps"])
    ce = _cross_entropy(x[:, :-1], params["lm_head"], tokens[:, 1:],
                        quantize)
    return ce + cfg["aux_loss_alpha"] * aux


def check(config, model, program_facts) -> dict:
    """What does not match between the configuration and the program's
    mla_moe ``ModelCfg``, as ``{key: (file, program)}``."""
    wrong = {k: (config[k], getattr(model, f))
             for k, f in MODEL_FIELDS.items()
             if config[k] != getattr(model, f)}
    wrong.update({k: (config[k], v) for k, v in FIXED.items()
                  if config[k] != v})
    wrong.update({k: (config[k], getattr(model, f))
                  for k, f in CLOSE.items()
                  if not math.isclose(config[k], getattr(model, f))})
    router = config["published"]["n_routed_experts"]
    if router != model.moe_experts:
        wrong["published.n_routed_experts"] = (router, model.moe_experts)
    eps = program_facts["rms_norm_eps"]
    if not math.isclose(config["kv_lora_norm_eps"], eps):
        wrong["kv_lora_norm_eps"] = (config["kv_lora_norm_eps"], eps)
    if model.family != "mla_moe":
        wrong["family"] = ("mla_moe", model.family)
    return wrong


def _leaves(shapes) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    return [(tuple(str(getattr(k, "key", k)) for k in path), s.shape)
            for path, s in flat]


def flops_per_token(config, shapes, seq: int) -> float:
    """Training FLOPs per token on this chip, forward and backward, no
    recompute: 6 times the matmul parameters a token uses (attention, the
    router, the shared experts, the dense FFN, the head over the slice, and
    of the held experts the share k / E a token routes to each: 0.75 of one
    expert's parameters at 6 of 64 over 8 held), plus causal attention's
    3 S H (QK width + V width) per layer (QK^T and AV, each 2 S H width
    forward for a full square, halved by the mask, times 3 for the
    backward). The embedding is a lookup, no matmul."""
    k = config["num_experts_per_tok"]
    routed = config["published"]["n_routed_experts"]
    n = 0.0
    for path, shape in _leaves(shapes):
        if path[-1].startswith("ln") or path[-1] == "embed":
            continue
        n += math.prod(shape) * (k / routed if "experts" in path else 1.0)
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    return 6.0 * n + 3.0 * seq * config["num_attention_heads"] * width \
        * config["num_hidden_layers"]


def expert_flops_per_token(config) -> float:
    """Training FLOPs per token of the held experts' grouped matmuls, from
    the configuration: each expert layer routes k of the E published
    experts a token, of which held / E are here on average; each is three
    matmuls of hidden x expert width, 6 FLOPs a parameter forward and
    backward."""
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    rows = config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["published"]["n_routed_experts"]
    return 6.0 * rows * 3 * config["hidden_size"] \
        * config["moe_intermediate_size"] * layers
