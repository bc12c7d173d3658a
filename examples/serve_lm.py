"""Batched serving example: prefill-free KV-cache decode on a reduced model.

    PYTHONPATH=src python examples/serve_lm.py

Drives each family's `decode_step`: batched requests, greedy sampling,
per-step cache update.
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.common import get_arch
from repro.models.api import build_model

ARCH = "qwen2_0_5b"
BATCH, STEPS, MAX_LEN = 8, 48, 128

arch = get_arch(ARCH).reduced()
bundle = build_model(arch.model)
params = bundle.init(jax.random.PRNGKey(0))
cache = bundle.init_cache(BATCH, MAX_LEN)
step = jax.jit(bundle.decode_step)

tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, 1), 0,
                            arch.model.vocab)
out = [tokens]
t0 = time.time()
for pos in range(STEPS):
    logits, cache = step(params, cache, tokens, jnp.int32(pos))
    tokens = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out.append(tokens)
dt = time.time() - t0
seqs = jnp.concatenate(out, axis=1)
print(f"arch={arch.model.name} (reduced) batch={BATCH}")
print(f"decoded {STEPS} steps in {dt:.2f}s "
      f"({BATCH * STEPS / dt:.0f} tok/s on CPU)")
print("sample token ids:", seqs[0, :16].tolist())
assert seqs.shape == (BATCH, STEPS + 1)
assert bool(jnp.all((seqs >= 0) & (seqs < arch.model.vocab)))
print("OK")
