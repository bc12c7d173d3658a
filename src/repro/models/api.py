"""Unified model API: ModelCfg + build_model -> ModelBundle.

ModelBundle is what the federated engine, launcher and tests consume:
init / loss_fn / decode_step / init_cache / per-step input specs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    name: str
    family: str     # dense | moe | mla_moe | hybrid | xlstm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    moe_experts: int = 0
    moe_topk: int = 0
    moe_ep: bool = False   # capacity MoE: one-hot einsum dispatch vs scatter
    qkv_bias: bool = False
    sliding_window: int = 0
    tie_embeddings: bool = True
    rope_theta: float = 1e4
    dtype: Any = jnp.float32
    n_img_tokens: int = 0       # vlm stub prefix length
    src_frac: float = 0.5       # encdec: fraction of seq_len used as source
    q_chunk: int = 512
    rms_eps: float = 1e-6       # the mla_moe family's layer and final
    #   RMSNorm eps; the other families run layers.rms_norm's default, 1e-6
    # -- the mla_moe family (DeepSeek-V3 layout): latent attention, leading
    #    dense layers, then expert layers; 0 leaves a field unused
    kv_lora_rank: int = 0       # width of the latent KV (c_kv)
    qk_nope_dim: int = 0        # per-head QK width without RoPE
    qk_rope_dim: int = 0        # per-head QK width with RoPE (k_pe shared)
    v_head_dim: int = 0         # per-head V width
    n_dense_layers: int = 0     # leading layers with a dense FFN of d_ff
    moe_d_ff: int = 0           # width of one routed or shared expert
    moe_shared: int = 0         # shared experts, run as one SwiGLU; the
    #   router scores by sigmoid
    moe_scale: float = 1.0      # routed_scaling_factor on the top-k weights
    moe_aux_alpha: float = 0.0  # weight of the sequence-wise balance loss
    moe_held: int = 0           # experts this chip holds (0: all of them)
    moe_held_start: int = 0     # the first of them; they are contiguous

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    def attn_cfg(self) -> L.AttnCfg:
        return L.AttnCfg(d_model=self.d_model, n_heads=self.n_heads,
                         n_kv_heads=self.n_kv_heads, d_head=self.d_head,
                         qkv_bias=self.qkv_bias,
                         sliding_window=self.sliding_window,
                         rope_theta=self.rope_theta, q_chunk=self.q_chunk)

    def mla_cfg(self) -> L.MLACfg:
        return L.MLACfg(d_model=self.d_model, n_heads=self.n_heads,
                        kv_lora_rank=self.kv_lora_rank,
                        qk_nope_dim=self.qk_nope_dim,
                        qk_rope_dim=self.qk_rope_dim,
                        v_head_dim=self.v_head_dim,
                        rope_theta=self.rope_theta, q_chunk=self.q_chunk)

    def expert_cfg(self) -> L.ExpertCfg:
        return L.ExpertCfg(d_model=self.d_model, d_ff=self.moe_d_ff,
                           n_experts=self.moe_experts, top_k=self.moe_topk,
                           n_shared=self.moe_shared,
                           held=self.moe_held or self.moe_experts,
                           held_start=self.moe_held_start,
                           scale=self.moe_scale)

    def attn_cfg_bidir(self) -> L.AttnCfg:
        return dataclasses.replace(self.attn_cfg(), causal=False,
                                   sliding_window=0)

    def param_count(self, params) -> int:
        return sum(p.size for p in jax.tree_util.tree_leaves(params))


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelCfg
    init: Callable                # (key) -> params
    loss_fn: Callable             # (params, batch) -> scalar
    decode_step: Callable         # (params, cache, tokens, position) -> (logits, cache)
    init_cache: Callable          # (batch, max_len) -> cache
    train_batch_spec: Callable    # (micro_batch, seq_len) -> pytree of ShapeDtypeStruct


def _lm_specs(cfg: ModelCfg):
    def spec(micro, seq):
        return {"tokens": jax.ShapeDtypeStruct((micro, seq), jnp.int32)}
    return spec


def build_model(cfg: ModelCfg) -> ModelBundle:
    if cfg.family in ("dense", "moe"):
        from repro.models import transformer as T
        return ModelBundle(
            cfg=cfg,
            init=lambda key: T.init_params(key, cfg),
            loss_fn=lambda p, b: T.loss_fn(p, b, cfg),
            decode_step=lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, m: T.init_cache(cfg, b, m),
            train_batch_spec=_lm_specs(cfg))

    if cfg.family == "mla_moe":
        from repro.models import mla_moe as M
        return ModelBundle(
            cfg=cfg,
            init=lambda key: M.init_params(key, cfg),
            loss_fn=lambda p, b: M.loss_fn(p, b, cfg),
            decode_step=lambda p, c, t, pos: M.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, m: M.init_cache(cfg, b, m),
            train_batch_spec=_lm_specs(cfg))

    if cfg.family == "hybrid":
        from repro.models import hybrid as Hy
        return ModelBundle(
            cfg=cfg,
            init=lambda key: Hy.init_params(key, cfg),
            loss_fn=lambda p, b: Hy.loss_fn(p, b, cfg),
            decode_step=lambda p, c, t, pos: Hy.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, m: Hy.init_cache(cfg, b, m),
            train_batch_spec=_lm_specs(cfg))

    if cfg.family == "xlstm":
        from repro.models import xlstm as X
        return ModelBundle(
            cfg=cfg,
            init=lambda key: X.init_params(key, cfg),
            loss_fn=lambda p, b: X.loss_fn(p, b, cfg),
            decode_step=lambda p, c, t, pos: X.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, m: X.init_cache(cfg, b, m),
            train_batch_spec=_lm_specs(cfg))

    if cfg.family == "encdec":
        from repro.models import encdec as E

        def spec(micro, seq):
            s_src = int(seq * cfg.src_frac)
            return {"embeds": jax.ShapeDtypeStruct((micro, s_src, cfg.d_model),
                                                   jnp.float32),
                    "tokens": jax.ShapeDtypeStruct((micro, seq - s_src),
                                                   jnp.int32)}

        return ModelBundle(
            cfg=cfg,
            init=lambda key: E.init_params(key, cfg),
            loss_fn=lambda p, b: E.loss_fn(p, b, cfg),
            decode_step=lambda p, c, t, pos: E.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, m: E.init_cache(cfg, b, m, src_len=2048),
            train_batch_spec=spec)

    if cfg.family == "vlm":
        from repro.models import transformer as T

        def vlm_loss(p, b):
            img = b["img_embeds"].astype(cfg.dtype)        # (B, P, D)
            txt = p["embed"][b["tokens"]]                  # (B, S-P, D)
            embeds = jnp.concatenate([img, txt], axis=1)
            B, P = img.shape[0], img.shape[1]
            S = embeds.shape[1]
            mask = jnp.concatenate(
                [jnp.zeros((B, P), jnp.float32), jnp.ones((B, S - P), jnp.float32)],
                axis=1)
            # tokens for the image prefix are a pad id (0): loss-masked out
            full_tokens = jnp.concatenate(
                [jnp.zeros((B, P), jnp.int32), b["tokens"]], axis=1)
            return T.loss_fn(p, {"tokens": full_tokens, "embeds": embeds,
                                 "loss_mask": mask}, cfg)

        def spec(micro, seq):
            P = cfg.n_img_tokens
            return {"img_embeds": jax.ShapeDtypeStruct((micro, P, cfg.d_model),
                                                       jnp.float32),
                    "tokens": jax.ShapeDtypeStruct((micro, seq - P), jnp.int32)}

        return ModelBundle(
            cfg=cfg,
            init=lambda key: T.init_params(key, cfg),
            loss_fn=vlm_loss,
            decode_step=lambda p, c, t, pos: T.decode_step(p, c, t, pos, cfg),
            init_cache=lambda b, m: T.init_cache(cfg, b, m),
            train_batch_spec=spec)

    raise ValueError(f"unknown family {cfg.family!r}")
