"""The mla_moe family (Moonlight-16B-A3B's layout) on the CPU at a small
size on seeded random weights: the program's loss and gradients against
the float32 reference, the expert layer's shares adding up to the uncut
layer, dropless routing under skew, the grouped matmul against a
per-expert einsum, the configuration check, the FLOP counts of the cell,
a whole harness run of a tiny mla_moe cell, and the ``expert_roofline``
reader.

The small model: 2 layers (one dense, one expert layer), d_model 64, 4
heads, latent 32, QK 16 + 8, V 16, 8 experts of width 32 with top-3 and 2
shared; a share holds 2 of the 8 experts. It runs in float32, so program
and reference differ only in the order of their arithmetic (and the
reference's HIGHEST precision, which the CPU gives both).
"""
import dataclasses
import json
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, inputs, program, work
from bench.reference import mla_moe
from bench.reference.dense import identity
from bench.tests import tiny

CELL = "moonlight-16b-a3b-l5.xsilo8k"

SMALL_MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                   d_ff=128, vocab=512, kv_lora_rank=32, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16, moe_d_ff=32, moe_experts=8,
                   moe_topk=3, dtype=jnp.float32)
SMALL_CONFIG = dict(hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=4, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    intermediate_size=128, moe_intermediate_size=32,
                    num_experts_per_tok=3, num_hidden_layers=2,
                    vocab_size=512, published={"n_routed_experts": 8})


def _cell_config_and_model():
    from repro.configs.common import get_arch
    config = harness.find_cell(tiny.ROOT, CELL)[2]
    model = get_arch(config["program"]["registry"]).model
    return config, dataclasses.replace(model, **config["program"]["model"])


def small(start: int = 0, held: int = 2, **model):
    """(configuration, ModelCfg) of the small model holding experts
    start .. start + held - 1 of 8."""
    config, base = _cell_config_and_model()
    config = {**config, **SMALL_CONFIG, "n_routed_experts": held,
              "held_expert_start": start, "torch_dtype": "float32"}
    m = dataclasses.replace(base, **{**SMALL_MODEL, "moe_held": held,
                                     "moe_held_start": start, **model})
    return config, m


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start, seq, q_chunk", [(0, 16, 512), (6, 64, 16)],
                         ids=["one_block", "kv_chunked"])
def test_loss_and_gradients_match_the_reference(start, seq, q_chunk):
    """Loss within 1e-5 relative and every leaf's gradient within 1e-4 of
    its norm: both are float32 on the CPU, so only the order of the sums
    differs (readings 1e-7 and 1e-6); a routing flip, a wrong scale or a
    left-out term reads 1e-2 or more."""
    from repro.models.api import build_model
    config, model = small(start, q_chunk=q_chunk)
    assert mla_moe.check(config, model, {"rms_norm_eps": 1e-6}) == {}
    bundle = build_model(model)
    params = inputs.params(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)),
                           2 ** 33 + start)
    tokens = jax.random.randint(jax.random.PRNGKey(seq), (2, seq), 0, 512)
    lp, gp = jax.value_and_grad(bundle.loss_fn)(params, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        lr, gr = jax.value_and_grad(
            lambda p: mla_moe.loss(config, p, tokens))(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    gaps = jax.tree.map(_rel, gp, gr)
    assert max(jax.tree_util.tree_leaves(gaps)) <= 1e-4, gaps


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _expert_layer(key, model, positive=None):
    """Full (8-expert) layer parameters and an input x (2, 16, 64). With
    ``positive``, the router scores that expert near 1 and all others near
    0 for every token."""
    from repro.models import layers as L
    full = dataclasses.replace(model, moe_held=8, moe_held_start=0)
    lp = L.experts_init(key, full.expert_cfg(), 1, jnp.float32)
    lp = jax.tree.map(lambda a: a[0], lp)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 64))
    if positive is not None:
        x = x + 3.0
        sign = (-jnp.ones((8,))).at[positive].set(1.0)
        lp["router"] = jnp.ones((64, 8)) / 64 * sign
    return lp, x


def _share(lp, start, held):
    return {**lp, "experts": jax.tree.map(lambda w: w[start:start + held],
                                          lp["experts"])}


def _reference_layer(model, lp, x, start=0, held=8):
    config, _ = small(start, held)
    with jax.default_matmul_precision("highest"):
        return mla_moe._experts(config, x, lp, identity)


def test_shares_add_up_to_the_uncut_layer():
    """The four shares' outputs (2 experts each), with the shared experts
    counted once, sum to the uncut layer's, the program's and the
    reference's alike; every share computes the same balance loss (its
    routing is over all 8 experts). Within 1e-5 of the output's norm:
    float32, order of the sums only."""
    from repro.models import layers as L
    _, model = small()
    lp, x = _expert_layer(jax.random.PRNGKey(5), model)
    shared = L.shared_experts(x, lp)
    parts, auxes = [], []
    for start in range(0, 8, 2):
        cfg = dataclasses.replace(model, moe_held=2,
                                  moe_held_start=start).expert_cfg()
        y, aux = L.routed_experts(x, _share(lp, start, 2), cfg)
        parts.append(y - shared)
        auxes.append(float(aux))
    total = sum(parts) + shared
    whole, whole_aux = L.routed_experts(
        x, lp, dataclasses.replace(model, moe_held=8).expert_cfg())
    ref, ref_aux = _reference_layer(model, lp, x)
    assert _rel(total, ref) <= 1e-5
    assert _rel(whole, ref) <= 1e-5
    assert np.allclose(auxes, float(ref_aux), rtol=1e-5)
    assert np.isclose(float(whole_aux), float(ref_aux), rtol=1e-5)
    # each share is its experts' part: no share is the whole
    for y in parts:
        assert _rel(y + shared, ref) > 1e-2


@pytest.mark.parametrize("positive", [4, 5])
def test_dropless_under_skew(positive):
    """Every token routes to one held expert (experts 4-5 held): 32 rows
    for that expert, where a capacity of 1.25 k T / E would keep 15. The
    share's output still equals the reference's, which runs the held
    experts over every token: no assignment is lost."""
    from repro.models import layers as L
    _, model = small(start=4)
    lp, x = _expert_layer(jax.random.PRNGKey(9), model, positive=positive)
    cfg = model.expert_cfg()
    w, idx, _ = L.route(x, lp["router"], cfg)
    assert bool(jnp.all(jnp.any(idx == positive, axis=-1)))
    T, k, E = 32, cfg.top_k, cfg.n_experts
    assert T > 1.25 * k * T / E
    y, _ = L.routed_experts(x, _share(lp, 4, 2), cfg)
    ref, _ = _reference_layer(model, _share(lp, 4, 2), x, start=4, held=2)
    assert _rel(y, ref) <= 1e-5
    routed = y - L.shared_experts(x, lp)
    assert bool(jnp.all(jnp.linalg.norm(routed, axis=-1) > 0))


@pytest.mark.parametrize("start, rows", [(0, 37), (3, 40), (6, 64)])
def test_grouped_matmul_matches_a_per_expert_einsum(start, rows):
    """Rows sorted by expert, 2 of 8 experts held from ``start``: the held
    experts' rows times their matrix, every other row (and each padding
    row past the groups) zero; the gradients of both operands the
    einsum's. Within 1e-5: float32, one dot product per element."""
    from repro.models import layers as L
    key = jax.random.PRNGKey(rows)
    experts = jnp.sort(jax.random.randint(key, (rows,), 0, 8))
    sizes = jnp.bincount(experts, length=8).astype(jnp.int32)
    pad = -rows % 8
    x = jax.random.normal(jax.random.fold_in(key, 1), (rows + pad, 64))
    w = jax.random.normal(jax.random.fold_in(key, 2), (2, 64, 32))
    cot = jax.random.normal(jax.random.fold_in(key, 3), (rows + pad, 32))
    local = jnp.pad(experts, (0, pad), constant_values=8) - start
    held = (local >= 0) & (local < 2)

    def einsum(x, w):
        y = jnp.einsum("md,mdf->mf", x, w[jnp.clip(local, 0, 1)],
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.where(held[:, None], y, 0.0)

    y = L.grouped_matmul(x, w, sizes, start)
    assert _rel(y, einsum(x, w)) <= 1e-5
    assert bool(jnp.all(y[~held] == 0))
    g = jax.grad(lambda a, b: jnp.sum(L.grouped_matmul(a, b, sizes, start)
                                      * cot), argnums=(0, 1))(x, w)
    want = jax.grad(lambda a, b: jnp.sum(einsum(a, b) * cot),
                    argnums=(0, 1))(x, w)
    assert _rel(g[0][:rows], want[0][:rows]) <= 1e-5
    assert _rel(g[1], want[1]) <= 1e-5


# ---------------------------------------------------------------------------
# the configuration check and the FLOP counts
# ---------------------------------------------------------------------------

def _other(v):
    if isinstance(v, bool) or v is None:
        return 1 if v is None else not v
    if isinstance(v, (int, float)):
        return v * 2 + 1
    return v + "x"


REFUSED = (list(mla_moe.MODEL_FIELDS) + list(mla_moe.FIXED)
           + list(mla_moe.CLOSE) + ["kv_lora_norm_eps"])


@pytest.mark.parametrize("key", REFUSED)
def test_check_refuses_a_wrong_key(key):
    config, model = _cell_config_and_model()
    config = {**config, key: _other(config[key])}
    assert set(mla_moe.check(config, model, {"rms_norm_eps": 1e-6})) == {key}
    with pytest.raises(ValueError, match=rf"mla_moe\.py.*'{key}'"):
        program.check_model(model, config, mla_moe)


@pytest.mark.parametrize("key, config_change, model_change", [
    ("published.n_routed_experts", {"published": {"n_routed_experts": 32}},
     {}),
    ("family", {}, {"family": "moe"}),
], ids=["router_width", "family"])
def test_check_refuses_what_does_not_run(key, config_change, model_change):
    config, model = _cell_config_and_model()
    config = {**config, **config_change}
    model = dataclasses.replace(model, **model_change)
    assert set(mla_moe.check(config, model, {"rms_norm_eps": 1e-6})) == {key}


def test_check_passes_the_cell():
    config, model = _cell_config_and_model()
    program.check_model(model, config, mla_moe)


def test_flop_counts_of_the_cell():
    """At the published widths (eval_shape of the cell's program): d, the
    FLOPs a token costs on this chip (mfu_pct) and those of the held
    experts' grouped matmuls (expert_roofline, through the reader's own
    path to the configuration)."""
    from bench.metrics import expert_roofline
    _, _, config, traffic = harness.find_cell(tiny.ROOT, CELL)
    prog = program.build(config, traffic, tiny.ROOT)
    assert prog.family.__name__ == "bench_family_mla_moe"
    assert work.n_params(prog.shapes) == 568_484_352
    assert prog.seq == 8192 and prog.tokens_per_round == 65_536
    assert prog.family.flops_per_token(config, prog.shapes, prog.seq) \
        == 1_653_866_496 + 629_145_600
    assert mla_moe.expert_flops_per_token(config) == 155_713_536
    run = SimpleNamespace(chips=1, work={"tokens_per_round": 65_536})
    assert expert_roofline.expert_flops_per_token(run, tiny.ROOT) \
        == 155_713_536
    # a run of silo4 (4 chips, 327,680 tokens a round) is no listed cell
    run = SimpleNamespace(chips=4, work={"tokens_per_round": 327_680})
    assert expert_roofline.expert_flops_per_token(run, tiny.ROOT) is None


# ---------------------------------------------------------------------------
# a whole run of a tiny mla_moe cell
# ---------------------------------------------------------------------------

#: limits of the tiny mla_moe cell (bf16, as the cell runs), set as the
#: dense tiny cell's are: above the largest readings of sound runs over 5
#: seeds (loss 5.9e-3, grad 0.020, change 0.017, sign 0.011: bf16 routing
#: flips read higher than the dense cell) and below the fp8 control's
#: (sign 0.067), half a batch's (grad 0.62), wrong client keys' (sign 0.32)
#: and a flipped update's (sign 0.35)
TINY_MLA_LIMITS = {"loss_gap": 0.015, "grad_gap": 0.05, "change_gap": 0.05,
                   "sign_gap": 0.025}


def tiny_mla_config() -> dict:
    config, _ = _cell_config_and_model()
    config = {**config, **SMALL_CONFIG, "name": "tiny-moonlight",
              "n_routed_experts": 2, "held_expert_start": 2}
    config["program"] = {"registry": "moonlight_16b_a3b", "model": {
        k: v for k, v in SMALL_MODEL.items() if k != "dtype"}}
    config["program"]["model"].update(moe_held=2, moe_held_start=2)
    return config


def test_tiny_mla_moe_cell_is_correct(tmp_path):
    root = tiny.make_root(tmp_path)
    tiny.add_cell(root, "tiny-moonlight.mix", tiny_mla_config(), "mix",
                  limits=TINY_MLA_LIMITS)
    hooks = harness.Hooks(skip_device_check=True,
                          peak={"bf16_flops_per_s": 1e12,
                                "hbm_bytes_per_s": 1e11})
    r = harness.run(root, "tiny-moonlight.mix", 2 ** 31 + 41, 0.2, False,
                    time.perf_counter(), hooks)
    assert r["correct"], r["checks"]
    json.dumps(r)


def test_expert_roofline_reads_the_grouped_matmul_kernels():
    """The reader sums the device time of the Pallas kernels whose name
    holds ``gmm`` (forward, input gradient and ``tgmm``), and reads nothing
    where the trace has none (the dense cells)."""
    from bench import trace
    from bench.metrics import expert_roofline as R
    kernel = 'custom-call(), custom_call_target="tpu_custom_call"'
    ops = [trace.Op("%transpose_jvp_jit_gmm___.1", 1.0, 1.5, kernel),
           trace.Op("%transpose_jvp_jit_tgmm___.2", 2.0, 2.5, kernel),
           trace.Op("%fusion.7", 3.0, 4.0, "fusion"),
           trace.Op("%gmm_like_fusion.1", 4.0, 5.0, "fusion")]
    ctx = SimpleNamespace(trace=trace.Trace({"/device:TPU:0": ops}, []),
                          win=(0.0, 10.0), rounds=2, chips=1,
                          work={"tokens_per_round": 65_536},
                          peak={"bf16_flops_per_s": 197e12})
    want = 100.0 * 155_713_536 * 65_536 * 2 / (197e12 * 1.0)
    assert np.isclose(R.read(ctx), want, rtol=1e-12)
    ctx.trace = trace.Trace({"/device:TPU:0": ops[2:]}, [])
    assert R.read(ctx) is None
    # kernels named so in a run of no listed cell: nothing to count them by
    ctx.trace, ctx.chips = trace.Trace({"/device:TPU:0": ops}, []), 4
    assert R.read(ctx) is None
