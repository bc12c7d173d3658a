"""Work counts from shapes: the configurations' sizes, and that no share the
per-layer metrics compute can pass 100% for work the program really does
(the dense family's FLOP count among them)."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness, program, work
from bench.reference import dense
from bench.tests import tiny


@pytest.mark.parametrize("cell, d", [("qwen2-0.5b.xdevice", 494_032_768),
                                     ("granite-3.0-8b-l2.xsilo", 599_818_240)])
def test_parameter_counts(cell, d):
    _, _, config, traffic = harness.find_cell(tiny.ROOT, cell)
    prog = program.build(config, traffic, tiny.ROOT)
    assert work.n_params(prog.shapes) == d
    # the tied embedding is counted once, as the head; norms and biases are
    # no matmul
    assert d - dense.n_matmul(prog.shapes) < 0.001 * d


def _tiny_program(seq):
    # one layer, and one chunk of the cross entropy: XLA's cost analysis
    # counts the body of a loop once, whatever its trip count
    config = tiny.tiny_config()
    config["num_hidden_layers"] = 1
    config["program"]["model"]["n_layers"] = 1
    traffic = tiny.tiny_traffic()
    traffic["train_args"]["seq-len"] = seq
    return config, program.build(config, traffic, tiny.ROOT)


@pytest.mark.parametrize("seq", [16, 64])
def test_model_flops_do_not_exceed_the_programs(seq):
    """FLOPs per token times tokens is at most what XLA counts for the
    program's own loss and gradient on the same batch (which also holds
    the recompute, the full attention square and the elementwise work),
    and more than half of it."""
    config, prog = _tiny_program(seq)
    batch = {"tokens": jax.ShapeDtypeStruct((2, seq), jnp.int32)}
    cost = jax.jit(jax.value_and_grad(prog.bundle.loss_fn)).lower(
        prog.shapes, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = prog.family.flops_per_token(config, prog.shapes, seq) * 2 * seq
    assert 0.5 * cost["flops"] < ours <= cost["flops"]


def test_kernel_bytes_are_at_most_what_the_kernels_move():
    """The encode reads the tile-padded f32 buffer and writes its padded
    bits; the reduce reads at least every live payload and writes the sum
    at least once. The least bytes are never above those."""
    tile = 8192
    for d in (1, 8191, 8192, 494_032_768, 599_818_240):
        dpad = -(-d // tile) * tile
        for n in (1, 4, 32):
            assert work.encode_bytes(n, d) <= n * (4 * dpad + dpad / 8)
            # a block of 8 clients is the kernel's least read
            assert work.reduce_bytes(n, d) <= \
                max(n, 8) * dpad / 8 + 4 * dpad


def test_flops_per_token_counts_attention_once_per_layer():
    shapes = {"embed": jax.ShapeDtypeStruct((10, 4), jnp.float32),
              "attn": {"wq": jax.ShapeDtypeStruct((2, 4, 4), jnp.float32)},
              "ln1": jax.ShapeDtypeStruct((2, 4), jnp.float32)}
    config = {"num_attention_heads": 2, "hidden_size": 4,
              "num_hidden_layers": 2}
    assert dense.n_matmul(shapes) == 40 + 32
    assert dense.flops_per_token(config, shapes, 8) == \
        6 * 72 + 6 * 8 * 2 * 2 * 2
