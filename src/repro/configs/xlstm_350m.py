"""xlstm-350m [arXiv:2405.04517; unverified]
24L d_model=1024 4H d_ff=0 vocab=50304; sLSTM + mLSTM blocks (3:1)."""
import jax.numpy as jnp
from repro.configs.common import ArchConfig
from repro.models.api import ModelCfg

ARCH = ArchConfig(
    arch_id="xlstm_350m",
    source="arXiv:2405.04517 (unverified)",
    model=ModelCfg(name="xlstm-350m", family="xlstm",
                   n_layers=24, d_model=1024, n_heads=4, n_kv_heads=4,
                   d_ff=0, vocab=50304, dtype=jnp.bfloat16),
    notes="recurrent: O(1) decode state")
