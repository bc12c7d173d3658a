"""Config schema and the architecture registry."""
from __future__ import annotations

import dataclasses
import importlib

import jax.numpy as jnp
from repro.models.api import ModelCfg


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    model: ModelCfg
    source: str                      # public-literature citation tag
    notes: str = ""

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        m = self.model
        vocab = min(m.vocab, 997)
        d_model = 64
        n_heads = 4
        n_kv = max(1, min(m.n_kv_heads, 2)) if m.n_kv_heads < m.n_heads else 4
        layers = {"hybrid": 8, "xlstm": 4}.get(m.family, 2)
        red = dataclasses.replace(
            m, n_layers=layers, d_model=d_model, n_heads=n_heads,
            n_kv_heads=n_kv, d_ff=0 if m.d_ff == 0 else 128, vocab=vocab,
            moe_experts=min(m.moe_experts, 4) if m.moe_experts else 0,
            moe_topk=min(m.moe_topk, 2) if m.moe_topk else 0,
            sliding_window=min(m.sliding_window, 8) if m.sliding_window else 0,
            n_img_tokens=4 if m.n_img_tokens else 0,
            kv_lora_rank=min(m.kv_lora_rank, 32),
            qk_nope_dim=min(m.qk_nope_dim, 16),
            qk_rope_dim=min(m.qk_rope_dim, 8),
            v_head_dim=min(m.v_head_dim, 16),
            n_dense_layers=min(m.n_dense_layers, 1),
            moe_d_ff=min(m.moe_d_ff, 32),
            moe_held=min(m.moe_held, 4), moe_held_start=0,
            dtype=jnp.float32)
        return dataclasses.replace(self, model=red)


_ARCH_IDS = [
    "granite_moe_1b_a400m",
    "llama4_scout_17b_a16e",
    "granite_3_8b",
    "qwen2_0_5b",
    "h2o_danube_3_4b",
    "qwen2_5_32b",
    "jamba_1_5_large_398b",
    "xlstm_350m",
    "internvl2_1b",
    "seamless_m4t_large_v2",
    "moonlight_16b_a3b",
]


def list_archs():
    return list(_ARCH_IDS)


def get_arch(arch_id: str) -> ArchConfig:
    arch_id = arch_id.replace("-", "_").replace(".", "_")
    if arch_id not in _ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {_ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.ARCH
