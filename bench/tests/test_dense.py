"""The dense family module as the cells use it: its configuration check
refuses what ``program.check_model`` refused before families were modules,
and its FLOP count reads the same for both cells."""
import dataclasses
import math

import pytest

from bench import harness, program
from bench.reference import dense
from bench.tests import tiny

QWEN2 = "qwen2-0.5b.xdevice"


def _config_and_model(cell=QWEN2):
    from repro.configs.common import get_arch
    config = harness.find_cell(tiny.ROOT, cell)[2]
    model = get_arch(config["program"]["registry"]).model
    return config, dataclasses.replace(model, **config["program"]["model"])


@pytest.mark.parametrize("key, config_change, model_change", [
    ("family", {}, {"family": "moe"}),
    ("family", {}, {"sliding_window": 4096}),
    ("embedding_multiplier", {"embedding_multiplier": 12.0}, {}),
    ("residual_multiplier", {"residual_multiplier": 0.22}, {}),
    ("logits_scaling", {"logits_scaling": 16.0}, {}),
    ("rms_norm_eps", {"rms_norm_eps": 1e-5}, {}),
    ("attention_multiplier", {"attention_multiplier": 0.0078125}, {}),
    ("intermediate_size", {"intermediate_size": 4865}, {}),
    ("num_key_value_heads", {"num_key_value_heads": 7}, {}),
], ids=["moe", "window", "embedding", "residual", "logits", "eps",
        "attention", "width", "kv_heads"])
def test_dense_check_refuses_what_does_not_run(key, config_change,
                                                model_change):
    config, model = _config_and_model()
    config = {**config, **config_change}
    model = dataclasses.replace(model, **model_change)
    assert set(dense.check(config, model, {"rms_norm_eps": 1e-6})) == {key}
    with pytest.raises(ValueError, match=rf"dense\.py.*'{key}'"):
        program.check_model(model, config, dense)


@pytest.mark.parametrize("cell", [QWEN2, "granite-3.0-8b-l2.xsilo"])
def test_dense_check_passes_the_cells(cell):
    config, model = _config_and_model(cell)
    program.check_model(model, config, dense)


@pytest.mark.parametrize("cell, d_matmul, flops", [
    (QWEN2, 493_961_216, 2_980_282_368),
    ("granite-3.0-8b-l2.xsilo", 599_797_760, 3_699_449_856),
])
def test_flops_per_token_of_the_cells(cell, d_matmul, flops):
    """At the published shapes (eval_shape of each cell's program): the
    count the cells' mfu_pct has read since it was defined."""
    _, _, config, traffic = harness.find_cell(tiny.ROOT, cell)
    prog = program.build(config, traffic, tiny.ROOT)
    assert prog.family.__name__ == "bench_family_dense"
    assert dense.n_matmul(prog.shapes) == d_matmul
    got = prog.family.flops_per_token(config, prog.shapes, prog.seq)
    assert got == flops
    m = prog.model
    assert math.isclose(got, 6 * d_matmul
                        + 6 * prog.seq * m.n_heads * m.d_head * m.n_layers)
