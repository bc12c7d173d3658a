"""moonlight-16b-a3b [hf:moonshotai/Moonlight-16B-A3B config.json; DeepSeek-V3
layout, arXiv:2412.19437]
27L d_model=2048 16H MLA (kv_lora_rank=512, QK 128+64, V 128, no query
LoRA), layer 0 dense d_ff=11264, layers 1-26 64 routed experts of 1408
(top-6, sigmoid, noaux_tc with one group, scaled 2.446) + 2 shared,
vocab=163840 untied, RMSNorm eps 1e-5, rope_theta 50000."""
import jax.numpy as jnp
from repro.configs.common import ArchConfig
from repro.models.api import ModelCfg

ARCH = ArchConfig(
    arch_id="moonlight_16b_a3b",
    source="hf:moonshotai/Moonlight-16B-A3B",
    model=ModelCfg(name="moonlight-16b-a3b", family="mla_moe",
                   n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
                   d_ff=11264, vocab=163840, tie_embeddings=False,
                   rope_theta=50000.0, dtype=jnp.bfloat16, rms_eps=1e-5,
                   kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
                   v_head_dim=128, n_dense_layers=1, moe_d_ff=1408,
                   moe_experts=64, moe_topk=6, moe_shared=2, moe_scale=2.446,
                   moe_aux_alpha=0.001),
    notes="MLA, 1 dense + 26 expert layers, all 64 experts held; "
          "e_score_correction_bias held at zero")
