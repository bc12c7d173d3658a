"""Fused weighted sign-reduce: kernel/oracle equivalence suite.

The server aggregation path has four implementations that must agree:

  wire.unpack_sum_dense   legacy dense-sign-matrix oracle (einsum)
  wire.unpack_sum         general weighted bit-sliced jnp path (CPU)
  wire.unpack_sum_mask    0/1-mask popcount fast path (CPU)
  kernels/zsign sign_reduce   fused Pallas kernel (TPU; interpret on CPU)

Exactness contract (see wire.py docstrings):
  * 0/1 masks: ALL paths are bit-exact vs the oracle — the sums are small
    integers, exactly representable in f32 under any association order.
  * arbitrary fp32 weights (EF per-client scales): the kernel and
    wire.unpack_sum share the same blocked client accumulation order, so
    they are bit-exact vs EACH OTHER; vs the dense oracle they agree to
    f32 rounding (different association order).
Covers weighted, masked (dead clients), EF per-client scales,
non-multiple-of-tile d, pack padding, and client counts off the kernel's
CLIENT_BLK boundary.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo_compat import given, settings, st

from repro.core import compression as C
from repro.core import wire
from repro.kernels.zsign import ops, ref
from repro.kernels.zsign import zsign as ZK


def _payload(rng, n, n_bytes):
    return jnp.asarray(rng.randint(0, 256, (n, n_bytes)), jnp.uint8)


def test_client_blk_constants_match():
    """wire.py mirrors the kernel's accumulation blocking — keep in sync."""
    assert wire.SIGN_REDUCE_CLIENT_BLK == ZK.CLIENT_BLK


@pytest.mark.parametrize("n", [1, 2, 7, 8, 9, 16, 33])
@pytest.mark.parametrize("n_bytes", [1, 13, 1024, 4097])
def test_mask_all_paths_bit_exact(n, n_bytes):
    """0/1 masks (incl. dead clients): every path == dense oracle exactly."""
    rng = np.random.RandomState(n * 1000 + n_bytes)
    packed = _payload(rng, n, n_bytes)
    mask = jnp.asarray(rng.randint(0, 2, n).astype(np.float32))
    want = np.asarray(wire.unpack_sum_dense(packed, mask))
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_sum(packed, mask)), want)
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_sum_mask(packed, mask)), want)
    np.testing.assert_array_equal(
        np.asarray(ops.sign_reduce(packed, mask)), want)


@pytest.mark.parametrize("n", [1, 3, 8, 20, 64])
@pytest.mark.parametrize("n_bytes", [5, 1024, 12501])
def test_fp32_weights_kernel_matches_jnp_bit_exact(n, n_bytes):
    """Arbitrary per-client fp32 weights (the EF case): the Pallas kernel
    and wire.unpack_sum accumulate in the same blocked client order and must
    agree bit-for-bit; both agree with the dense oracle to f32 rounding."""
    rng = np.random.RandomState(n * 7919 + n_bytes)
    packed = _payload(rng, n, n_bytes)
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    got_k = np.asarray(ops.sign_reduce(packed, w))
    got_j = np.asarray(wire.unpack_sum(packed, w))
    np.testing.assert_array_equal(got_k, got_j)
    want = np.asarray(wire.unpack_sum_dense(packed, w))
    np.testing.assert_allclose(got_k, want, rtol=1e-5,
                               atol=1e-6 * max(1, n))
    # the two dense-matrix oracle formulations are themselves identical
    np.testing.assert_array_equal(
        np.asarray(ref.sign_reduce_ref(packed, w)), want)


def test_kernel_zero_weight_rows_contribute_nothing():
    """Dead clients (weight 0) drop out exactly, matching a physically
    smaller stack — including when masking changes the padded client count."""
    rng = np.random.RandomState(0)
    packed = _payload(rng, 11, 2048)
    w = jnp.asarray(rng.rand(11).astype(np.float32))
    mask = jnp.asarray([1, 0, 1, 1, 0, 0, 1, 1, 1, 0, 1], jnp.float32)
    got = np.asarray(ops.sign_reduce(packed, w * mask))
    live = np.asarray(mask) > 0
    want = np.asarray(ops.sign_reduce(
        packed[np.where(live)[0]],
        jnp.asarray(np.asarray(w)[live])))
    # same blocked order only when live clients are a prefix — compare via
    # the jnp path which is bit-identical to the kernel per construction
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(
        got, np.asarray(wire.unpack_sum(packed, w * mask)))


@pytest.mark.parametrize("weights", ["mask", "fractional"])
@pytest.mark.parametrize("n", [1, 3, 8, 13, 16])
def test_pallas_fold_into_acc_matches_jnp_bit_exact(n, weights):
    """A carried flat sum folds into the kernel in place, in the left-fold
    order of wire.unpack_sum, so the two agree to the bit for any weights.
    33 tiles: more than one grid step, the last one ragged."""
    n_bytes = 33 * 1024
    rng = np.random.RandomState(n * 101 + len(weights))
    packed = _payload(rng, n, n_bytes)
    w = (rng.randint(0, 2, n) if weights == "mask" else rng.randn(n))
    w = jnp.asarray(w.astype(np.float32))
    acc = jnp.asarray(rng.randn(8 * n_bytes).astype(np.float32))
    got = C.sign_reduce(packed, w, "pallas", acc=acc)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(wire.unpack_sum(packed, w, acc)))


def test_pallas_fold_off_tile_width():
    """A width off the tile pads the carried sum with the bytes, and only
    the leading coordinates come back."""
    rng = np.random.RandomState(5)
    packed = _payload(rng, 3, 1000)
    w = jnp.asarray(rng.randn(3).astype(np.float32))
    acc = jnp.asarray(rng.randn(8000).astype(np.float32))
    got = ops.sign_reduce(packed, w, acc)
    assert got.shape == (8000,)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(wire.unpack_sum(packed, w, acc)))


def _outer_eqns(jaxpr):
    """Every equation, nested jits included, but not a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _outer_eqns(inner)


@pytest.mark.parametrize("n", [1, 3])
def test_pallas_fold_is_in_place(n):
    """The Pallas route hands the carried sum to the kernel as the operand
    its output aliases: no add of the sum after the kernel, and a stack
    under CLIENT_BLK is not padded with dead rows."""
    n_bytes = 2 * 1024
    jaxpr = jax.make_jaxpr(
        lambda p, w, a: C.sign_reduce(p, w, "pallas", acc=a))(
            jnp.zeros((n, n_bytes), jnp.uint8), jnp.ones((n,)),
            jnp.zeros((8 * n_bytes,)))
    eqns = list(_outer_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    (alias,) = calls[0].params["input_output_aliases"]
    assert alias[1] == 0
    aliased = calls[0].invars[alias[0]]
    assert aliased.aval.shape == (8 * n_bytes // 128, 128)
    assert aliased.aval.dtype == jnp.float32
    assert "add" not in names
    assert "pad" not in names


@pytest.mark.parametrize("n_tiles,blk,want", [
    (1, 1, 1), (13, 1, 13), (32, 1, 32), (33, 1, 32), (60307, 1, 32),
    (73220, 8, 32), (60307, 3, 32)])
def test_reduce_tiles_per_step(n_tiles, blk, want):
    """Tiles per grid step: a power of two under the VMEM budget, or the
    whole buffer when it is shorter."""
    assert ZK.reduce_tiles(n_tiles, blk) == want


@pytest.mark.parametrize("d", [8, 64, 8192, 8192 * 2 + 136, 100_008])
def test_tile_and_pack_padding(d):
    """d off the 8192-element kernel tile: padded bytes/clients never leak
    into the leading d coordinates."""
    rng = np.random.RandomState(d)
    n = 5
    n_bytes = d // 8
    packed = _payload(rng, n, n_bytes)
    mask = jnp.ones((n,), jnp.float32)
    got = ops.sign_reduce(packed, mask)
    assert got.shape == (d,)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(wire.unpack_sum_dense(packed, mask)))


def test_efsign_scales_through_all_backends():
    """EF aggregation (weights = mask * per-client scale) is identical
    through jnp and pallas backends, and rounding-close to dense."""
    d, n = 3001, 6
    rng = np.random.RandomState(3)
    flats = [jnp.asarray(rng.randn(d), jnp.float32) * (i + 0.5)
             for i in range(n)]
    encs = []
    efsign = C.Pipeline("ef|zsign")
    for f in flats:
        e, _ = efsign.encode(None, f, efsign.init_state(d))
        encs.append(e)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *encs)
    mask = jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32)
    outs = {}
    for backend in ["jnp", "pallas", "dense"]:
        comp = C.Pipeline(f"ef|zsign(agg_backend={backend})")
        outs[backend] = np.asarray(comp.aggregate(stacked, mask, d))
    np.testing.assert_array_equal(outs["jnp"], outs["pallas"])
    np.testing.assert_allclose(outs["jnp"], outs["dense"], rtol=1e-5,
                               atol=1e-5)


def _with_opts(spec: str, opts: str) -> str:
    """Append codec kwargs to the last stage of a pipeline spec string."""
    if spec.endswith(")"):
        return f"{spec[:-1]},{opts})"
    return f"{spec}({opts})"


@pytest.mark.parametrize("spec", ["zsign(z=1,sigma=0.5)", "stosign",
                                  "zsign_packed(z=1,sigma=0.5)"])
def test_mask_compressors_identical_across_backends(spec):
    """zsign/stosign/zsign_packed aggregation is bit-identical through every
    backend (mask weights -> integer sums)."""
    d, n = 10_007, 9
    rng = np.random.RandomState(11)
    spec_flat = jnp.asarray(rng.randn(d), jnp.float32)
    key = jax.random.PRNGKey(0)
    encs = []
    base = C.Pipeline(spec)
    for i in range(n):
        e, _ = base.encode(jax.random.fold_in(key, i), spec_flat, None)
        encs.append(e)
    stacked = jnp.stack(encs)
    mask = jnp.asarray(rng.randint(0, 2, n).astype(np.float32))
    outs = []
    for backend in C.AGG_BACKENDS:
        comp = C.Pipeline(_with_opts(spec, f"agg_backend={backend}"))
        outs.append(np.asarray(comp.aggregate(stacked, mask, d)))
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_fractional_weights_correct_on_every_backend():
    """Regression: data-size-proportional (non-0/1) client weights through
    ZSign/StoSign aggregate must be weighted correctly on every backend —
    the popcount membership specialization must never be auto-dispatched."""
    rng = np.random.RandomState(2)
    packed = _payload(rng, 4, 8)
    w = jnp.asarray([0.5, 0.5, 1.0, 0.0], jnp.float32)
    want = np.asarray(wire.unpack_sum_dense(packed, w))
    for name in ["zsign", "stosign"]:
        for backend in ["jnp", "pallas", "dense"]:
            comp = C.Pipeline(f"{name}(agg_backend={backend})")
            got = np.asarray(comp.aggregate(packed, w, 64))
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                       err_msg=f"{name}/{backend}")


def test_unknown_backend_raises():
    packed = jnp.zeros((2, 8), jnp.uint8)
    with pytest.raises(ValueError, match="unknown agg backend"):
        C.sign_reduce(packed, jnp.ones((2,)), "nope")


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _walk_eqns(inner)


def test_no_dense_sign_matrix_in_aggregate_jaxpr():
    """The (n_clients, d) fp32/int8 sign intermediate must not appear
    anywhere in the sign-family server aggregation path (including inside
    nested jits)."""
    n, n_bytes = 16, 8192
    d = n_bytes * 8
    for name, backend in [("zsign", "jnp"), ("stosign", "jnp"),
                          ("efsign", "jnp"), ("zsign", "pallas")]:
        spec = "ef|zsign" if name == "efsign" else name
        comp = C.Pipeline(_with_opts(spec, f"agg_backend={backend}"))
        if name == "efsign":
            payload = {"packed": jnp.zeros((n, n_bytes), jnp.uint8),
                       "scale": jnp.ones((n,))}
            fn = lambda p, m: comp.aggregate(p, m, d)
            jaxpr = jax.make_jaxpr(fn)(payload, jnp.ones((n,)))
        else:
            jaxpr = jax.make_jaxpr(
                lambda p, m: comp.aggregate(p, m, d))(
                    jnp.zeros((n, n_bytes), jnp.uint8), jnp.ones((n,)))
        for eqn in _walk_eqns(jaxpr.jaxpr):
            for var in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(var, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                if (tuple(aval.shape)[-2:] == (n, d)
                        and aval.dtype in (jnp.float32, jnp.int8)):
                    raise AssertionError(
                        f"{name}/{backend}: dense (n_clients, d) "
                        f"{aval.dtype} sign matrix in aggregation jaxpr: "
                        f"{eqn}")


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=40),
       st.integers(min_value=1, max_value=5000))
def test_property_mask_exact_any_shape(n, n_bytes):
    rng = np.random.RandomState(n * 31 + n_bytes)
    packed = _payload(rng, n, n_bytes)
    mask = jnp.asarray(rng.randint(0, 2, n).astype(np.float32))
    want = np.asarray(wire.unpack_sum_dense(packed, mask))
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_sum_mask(packed, mask)), want)
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_sum(packed, mask)), want)


@pytest.mark.parametrize("n", [247, 248, 249, 255, 256, 257])
def test_mask_popcount_acc_dtype_boundary(n):
    """The popcount path's uint8 block accumulator is only safe while the
    PADDED client count (n + (-n) % 8 pad rows) fits in 255 — the all-ones
    payload at full participation drives every per-coordinate count to its
    maximum, so any accumulator overflow shows up as a wrapped sum here.
    Regression for the old ``n <= 255`` bound, which ignored pad rows."""
    n_bytes = 64
    packed = jnp.ones((n, n_bytes), jnp.uint8) * jnp.uint8(0xFF)
    mask = jnp.ones((n,), jnp.float32)
    want = np.full(n_bytes * 8, float(n), np.float32)  # all +1 votes
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_sum_mask(packed, mask)), want)
    # partial masks near the boundary stay exact too
    rng = np.random.RandomState(n)
    mask = jnp.asarray(rng.randint(0, 2, n).astype(np.float32))
    pk = _payload(rng, n, n_bytes)
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_sum_mask(pk, mask)),
        np.asarray(wire.unpack_sum_dense(pk, mask)))
