"""Every Pallas kernel compiles for a TPU v5e chip at the widths the round
gives it.

The TPU compiler is installed with jax, and it compiles for a chip that is
described rather than attached, so these tests need no accelerator. They see
what interpret mode cannot: block shapes the chip's tiling refuses, lowering
rules Mosaic lacks, VMEM and HBM limits. Widths: the flat qwen2-0.5B
pseudo-gradient (one client's full encode and its in-place fold into the
server's sum), 16-client stacks for the batched encode and the
sign-reduce, and the Moonlight cell's expert layer for the grouped matmul.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.common import get_arch
from repro.kernels.common import FLAT_ROWS, LANE, ROWS_BLK, TILE
from repro.kernels.efsign import efsign as EK
from repro.kernels.efsign import ops as eops
from repro.kernels.zsign import ops
from repro.kernels.zsign import zsign as K
from repro.models.api import build_model

N_CLIENTS = 16
#: per-client width of the 16-client encode stack: 16 full-width clients
#: (16 x 2 GB of f32 input) would not fit one chip's 16 GB
STACK_TILES = (1 << 24) // TILE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def d():
    """Coordinates of the qwen2-0.5B flat buffer: whole lanes, not whole
    tiles."""
    bundle = build_model(get_arch("qwen2_0_5b").model)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    d = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    assert d > 490_000_000 and d % LANE == 0 and d % TILE
    return d


@pytest.fixture(scope="module")
def n_tiles(d):
    """8192-element tiles of the tile-padded qwen2-0.5B flat buffer."""
    return -(-d // TILE)


@pytest.fixture(scope="module")
def shape(one_chip):
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("z", [1, 0, None])
def test_compress_rng_one_client(shape, d, z):
    """One client's unpadded flat view, its last block ragged."""
    _compile(lambda x, k, s: K.compress_rng_pallas(x, k, s, z=z,
                                                   interpret=False),
             shape((1, d // LANE, LANE), jnp.float32),
             shape((1, 2), jnp.uint32), shape((1,), jnp.float32))


def test_compress_rng_client_stack(shape):
    _compile(lambda x, k, s: K.compress_rng_pallas(x, k, s, z=1,
                                                   interpret=False),
             shape((N_CLIENTS, STACK_TILES * FLAT_ROWS, LANE), jnp.float32),
             shape((N_CLIENTS, 2), jnp.uint32),
             shape((N_CLIENTS,), jnp.float32))


def test_encode_needs_only_its_input_and_output(shape, n_tiles):
    """The fused encode at full width allocates the padded input and the
    packed output, and no f32 noise surface."""
    d = n_tiles * TILE - 100
    compiled = _compile(
        lambda x, k: ops.zsign_encode_fused(x, k, 0.01, z=1, interpret=False),
        shape((d,), jnp.float32), shape((2,), jnp.uint32))
    mem = compiled.memory_analysis()
    padded_in, packed_out = 4 * n_tiles * TILE, n_tiles * TILE // 8
    assert mem.temp_size_in_bytes <= padded_in + packed_out + (1 << 20), mem


def test_encode_of_whole_lanes_copies_nothing(shape, d, n_tiles):
    """At qwen2-0.5B's own width, a multiple of 128 and not of the tile, the
    kernel reads the client's buffer in place: no padded copy of it."""
    compiled = _compile(
        lambda x, k: ops.zsign_encode_fused(x, k, 0.01, z=1, interpret=False),
        shape((d,), jnp.float32), shape((2,), jnp.uint32))
    mem = compiled.memory_analysis()
    packed_out = n_tiles * TILE // 8
    assert mem.temp_size_in_bytes <= packed_out + (1 << 20), mem


def test_sign_reduce(shape, n_tiles):
    _compile(lambda p, w: K.sign_reduce_pallas(p, w, interpret=False),
             shape((N_CLIENTS, n_tiles * ROWS_BLK, LANE), jnp.uint8),
             shape((N_CLIENTS,), jnp.float32))


def test_sign_reduce_folds_one_client_in_place(shape, n_tiles):
    """The streamed fold of one full-width client into the donated f32 sum
    writes the sum in place: no copy of it, no pad of the payload to a
    block of CLIENT_BLK clients."""
    n_bytes = n_tiles * TILE // 8
    compiled = jax.jit(lambda p, w, a: ops.sign_reduce(p, w, a,
                                                       interpret=False),
                       donate_argnums=2).lower(
        shape((1, n_bytes), jnp.uint8), shape((1,), jnp.float32),
        shape((8 * n_bytes,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * n_tiles * TILE, mem
    assert mem.temp_size_in_bytes <= 4 << 20, mem


def test_ef_update(shape, n_tiles):
    view = shape((n_tiles * FLAT_ROWS, LANE), jnp.float32)
    _compile(lambda g, e, s: EK.ef_update_pallas(g, e, s, interpret=False),
             view, view, shape((), jnp.float32))


def test_ef_encode_op(shape, n_tiles):
    flat = shape((n_tiles * TILE,), jnp.float32)
    _compile(lambda g, e: eops.ef_sign_encode(g, e, 0.5, interpret=False),
             flat, flat)


def test_compress_dense_noise(shape, n_tiles):
    view = shape((n_tiles * FLAT_ROWS, LANE), jnp.float32)
    _compile(lambda x, n, s: K.compress_pallas(x, n, s, interpret=False),
             view, view, shape((), jnp.float32))


def test_grouped_matmul_of_the_expert_layer(shape, monkeypatch):
    """The held experts' grouped matmul at the Moonlight cell's widths (8 of
    64 experts, 8,192 tokens x top-6 rows, 2,048 x 1,408), both gradients:
    the megablox kernels compile for the chip, under names that hold what
    ``expert_roofline`` looks for in a trace, ``gmm`` (under a gradient
    transform, ``transpose_jvp_jit_gmm___``; ``tgmm`` for the weights')."""
    from repro.kernels import common
    from repro.models import layers as L
    monkeypatch.setattr(common, "interpret_mode", lambda: False)

    def loss(x, w, sizes):
        return jnp.sum(L.grouped_matmul(x, w, sizes, 0).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        shape((49152, 2048), jnp.bfloat16), shape((8, 2048, 1408), jnp.bfloat16),
        shape((64,), jnp.int32)).compile()
    names = set(re.findall(r"%(\w+)\.\d+ = [^\n]*tpu_custom_call",
                           compiled.as_text()))
    assert names and all("gmm" in n for n in names), names
    assert any("tgmm" in n for n in names), names
