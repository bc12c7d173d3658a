"""Per-architecture smoke tests: REDUCED same-family configs, one forward /
train step on CPU, asserting output shapes + finiteness."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.common import get_arch, list_archs
from repro.core import compression, fedavg
from repro.models.api import build_model


def make_batch(spec, vocab, key):
    return jax.tree.map(
        lambda s: (jax.random.randint(key, s.shape, 0, vocab)
                   if s.dtype == jnp.int32
                   else jax.random.normal(key, s.shape, s.dtype)), spec)


@pytest.mark.parametrize("arch_id", list_archs())
def test_reduced_config_train_step(arch_id):
    arch = get_arch(arch_id).reduced()
    bundle = build_model(arch.model)
    key = jax.random.PRNGKey(0)
    params = bundle.init(key)
    spec = bundle.train_batch_spec(2, 32)
    batch = make_batch(spec, arch.model.vocab, key)
    loss, grads = jax.jit(jax.value_and_grad(bundle.loss_fn))(params, batch)
    assert jnp.isfinite(loss), f"{arch_id}: non-finite loss"
    gn = sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))
    assert jnp.isfinite(gn), f"{arch_id}: non-finite grads"
    assert float(loss) > 0.0


@pytest.mark.parametrize("arch_id", list_archs())
def test_reduced_config_decode_step(arch_id):
    arch = get_arch(arch_id).reduced()
    bundle = build_model(arch.model)
    key = jax.random.PRNGKey(1)
    params = bundle.init(key)
    cache = bundle.init_cache(2, 64)
    tokens = jnp.zeros((2, 1), jnp.int32)
    logits, cache2 = jax.jit(bundle.decode_step)(params, cache, tokens,
                                                 jnp.int32(5))
    assert logits.shape == (2, 1, arch.model.vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch_id}: non-finite logits"
    # cache structure preserved
    assert (jax.tree_util.tree_structure(cache2)
            == jax.tree_util.tree_structure(cache))


@pytest.mark.parametrize("arch_id", ["qwen2_0_5b", "granite_moe_1b_a400m",
                                     "xlstm_350m", "moonlight_16b_a3b"])
def test_reduced_fed_round(arch_id):
    """Full federated round on a reduced model: 4 clients, E=2, z-sign."""
    arch = get_arch(arch_id).reduced()
    bundle = build_model(arch.model)
    comp = compression.Pipeline("zsign(z=1,sigma=0.05)")
    cfg = fedavg.FedConfig(n_clients=4, local_steps=2, client_lr=0.05,
                           server_lr=0.5)
    step = jax.jit(fedavg.build_round_step(bundle.loss_fn, comp, cfg))
    params = bundle.init(jax.random.PRNGKey(0))
    state = fedavg.init_server_state(params, cfg, comp, jax.random.PRNGKey(1))
    spec = fedavg.make_batch_spec(cfg, bundle.train_batch_spec(2, 32))
    batch = make_batch(spec, arch.model.vocab, jax.random.PRNGKey(2))
    mask = jnp.ones((1, 4))
    l0 = None
    for i in range(5):
        state, metrics = step(state, batch, mask)
        assert jnp.isfinite(metrics.loss)
        if l0 is None:
            l0 = float(metrics.loss)
    # same batch each round: loss must drop (memorization)
    assert float(metrics.loss) < l0


def test_decode_matches_forward_dense():
    """KV-cache decode == teacher-forced forward logits, position by position."""
    arch = get_arch("qwen2_0_5b").reduced()
    bundle = build_model(arch.model)
    from repro.models import transformer as T
    params = bundle.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              arch.model.vocab)
    full_logits, _ = T.forward(params, toks, arch.model)
    cache = bundle.init_cache(2, 8)
    outs = []
    for t in range(8):
        lg, cache = bundle.decode_step(params, cache, toks[:, t:t + 1],
                                       jnp.int32(t))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    assert jnp.max(jnp.abs(dec_logits - full_logits)) < 2e-2


def test_decode_matches_forward_latent_attention():
    """Decode through the latent cache (c_kv and k_pe per position) ==
    teacher-forced forward logits, position by position, through the dense
    layer and the expert layer. Both in f32 on the CPU: 1e-4."""
    arch = get_arch("moonlight_16b_a3b").reduced()
    bundle = build_model(arch.model)
    from repro.models import mla_moe as M
    params = bundle.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              arch.model.vocab)
    hidden, _ = M.forward_hidden(params, toks, arch.model)
    full_logits = hidden @ M.lm_head(params, arch.model)
    cache = bundle.init_cache(2, 8)
    step = jax.jit(bundle.decode_step)
    outs = []
    for t in range(8):
        lg, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    assert jnp.max(jnp.abs(dec_logits - full_logits)) < 1e-4
