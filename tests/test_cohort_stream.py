"""Streaming massive-cohort engine: stream-vs-vmap equivalence suite.

The round driver has two execution plans over the same round math
(core/fedavg.py): ``vmap`` (one vmap over all parallel clients) and
``stream`` (shard-sized slices under a lax.scan folding into ONE wire
accumulator). Contract:

  * per-client PRNG keys derive from the GLOBAL client index
    (noise.client_keys), so randomness is invariant to the shard partition;
  * 0/1 participation masks: the two plans are BIT-identical for any shard
    size (integer sign sums / dyadic scatter sums associate exactly);
  * fp32 aggregation weights (EF per-client scales): bit-identical when the
    shard size is a multiple of wire.SIGN_REDUCE_CLIENT_BLK (the fold
    continues the same blocked accumulation order), f32-rounding-close
    otherwise;
  * the streaming jaxpr never materializes an (n_total, d) f32 buffer or a
    full-cohort uint8 payload stack — peak wire memory is O(shard * d / 8);
  * ``auto`` (and a bare ``stream``) gate small rounds back to the vmap
    plan; an explicit ``stream(shard=K)`` always streams.

Multi-device (``stream(devices=D)``, shard_map over a 1-D ``clients`` mesh):

  * 0/1 masks: D in {1, 2, 4, 8} is BIT-identical to the vmap plan and the
    single-device stream at any shard size — integer sign sums stay exact
    under the cross-device psum, and counter-based keys are placement-
    invariant;
  * fp32 EF scale weights: residuals (per-client, never summed across
    devices) are bit-identical per round; params are f32-close (the psum
    meets the per-device partial sums in a different association order than
    the sequential fold). ``ef|zsign(scale=none)`` has 0/1 weights, so it is
    fully exact multi-round at any D;
  * the ONLY cross-device collective in the round jaxpr is an O(d) fp32
    psum of the wire accumulator (plus the scalar loss psum) — never a
    payload stack, never per-client data (the jaxpr pin below).

These run under XLA_FLAGS=--xla_force_host_platform_device_count=8 (the CI
multi-device smoke job); with fewer visible devices they skip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.core import fedavg, wire
from repro.core import noise as Z
from repro.core.context import (COHORT_DEVICES_AUTO, STREAM_AUTO_MIN_ELEMS,
                                STREAM_DEFAULT_SHARD, STREAM_SHARD_AUTO,
                                STREAM_SHARD_MAX, STREAM_SHARD_MIN,
                                CohortPolicy, RoundContext)
from repro.fed.sampling import CohortSampler

_DC = jax.device_count()


def _devices(d):
    """Parametrize a device count, skipping when the host shows fewer
    devices (run under XLA_FLAGS=--xla_force_host_platform_device_count=8
    to unskip — see the CI multi-device smoke job)."""
    return pytest.param(d, marks=pytest.mark.skipif(
        _DC < d, reason=f"needs {d} devices (have {_DC}); set "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={d}"))


# ---------------------------------------------------------------------------
# policy parsing + the auto-gate
# ---------------------------------------------------------------------------

def test_cohort_policy_parse():
    assert CohortPolicy.parse("auto") == CohortPolicy("auto")
    assert CohortPolicy.parse("vmap") == CohortPolicy("vmap")
    assert CohortPolicy.parse("stream") == CohortPolicy("stream")
    pol = CohortPolicy.parse("stream(shard=16,unroll=2)")
    assert (pol.mode, pol.shard, pol.unroll) == ("stream", 16, 2)
    # idempotent on an already-parsed policy
    assert CohortPolicy.parse(pol) is pol
    # shard=0 is VALID ("engine default"), so it still auto-gates
    assert CohortPolicy.parse("stream(shard=0)").shard == 0
    # the device axis and the shard/feed sentinels
    assert CohortPolicy.parse("stream(shard=auto)").shard == STREAM_SHARD_AUTO
    assert CohortPolicy.parse("stream(devices=4)").devices == 4
    assert CohortPolicy.parse(
        "stream(devices=auto)").devices == COHORT_DEVICES_AUTO
    pol = CohortPolicy.parse("stream(shard=auto,devices=auto,unroll=2)")
    assert pol == CohortPolicy("stream", STREAM_SHARD_AUTO, 2,
                               COHORT_DEVICES_AUTO, "device")
    assert CohortPolicy.parse("stream(feed=host)").feed == "host"
    assert CohortPolicy.parse("stream(shard=8,feed=device)").feed == "device"
    for bad in ["nope", "stream(shard=a)", "vmap(shard=2)",
                "stream(shard=2,unroll=0)", "stream(frac=2)",
                "stream(unroll=auto)",       # auto is shard/devices-only
                "vmap(devices=2)",           # device axis is stream-only
                "auto(feed=host)",
                "stream(feed=nope)",
                "stream(devices=2,feed=host)"]:  # host feed is single-device
        with pytest.raises(ValueError):
            CohortPolicy.parse(bad)
    with pytest.raises(ValueError):
        RoundContext(cohort="stream(shard=-1)")
    with pytest.raises(ValueError):
        RoundContext(cohort="stream(devices=-2)")


def test_resolve_cohort_gating():
    big = STREAM_AUTO_MIN_ELEMS  # elems threshold: total * n_coords
    plan = lambda shard, unroll=1, devices=1, feed="device": \
        fedavg.CohortPlan("stream", shard, unroll, devices, feed)
    # explicit vmap never streams
    assert fedavg.resolve_cohort("vmap", 1 << 20, 1 << 20) == fedavg.VMAP_PLAN
    # auto below the threshold keeps the vmap plan
    assert fedavg.resolve_cohort("auto", 8, 100) == fedavg.VMAP_PLAN
    assert fedavg.resolve_cohort("stream", 8, 100) == fedavg.VMAP_PLAN
    # auto above the threshold streams at the memory-budget shard size
    d = big // 1024
    assert fedavg.resolve_cohort("auto", 4096, d) == \
        plan(fedavg.auto_shard_size(d))
    # explicit shard forces streaming below the threshold
    assert fedavg.resolve_cohort("stream(shard=4)", 8, 100) == plan(4)
    # shard clamps to the cohort; forced single-shard still streams
    assert fedavg.resolve_cohort("stream(shard=64)", 10, 100) == plan(10)
    # unroll rides along
    assert fedavg.resolve_cohort("stream(shard=4,unroll=3)", 8, 100) == \
        plan(4, unroll=3)
    # shard=auto forces streaming at the auto-tuned (clamped) size
    assert fedavg.resolve_cohort("stream(shard=auto)", 8, 100) == plan(8)
    # feed=host forces streaming and survives into the plan
    assert fedavg.resolve_cohort("stream(feed=host)", 8, 100) == \
        plan(8, feed="host")
    # auto where one (auto-sized) shard covers the whole cohort -> vmap
    assert fedavg.resolve_cohort("auto", 4, 1 << 22) == fedavg.VMAP_PLAN
    # devices clamp to the shard count (no all-padding devices) and
    # validate against the visible device count
    got = fedavg.resolve_cohort("stream(shard=4,devices=auto)", 8, 100)
    assert got == plan(4, devices=min(jax.device_count(), 2))
    with pytest.raises(ValueError, match="device"):
        fedavg.resolve_cohort(
            f"stream(shard=4,devices={jax.device_count() + 1})", 8, 100)


def test_auto_shard_size():
    blk = wire.SIGN_REDUCE_CLIENT_BLK
    # no model info -> the static default
    assert fedavg.auto_shard_size(0) == STREAM_DEFAULT_SHARD
    # tiny models clamp high, huge models clamp low
    assert fedavg.auto_shard_size(100) == STREAM_SHARD_MAX
    assert fedavg.auto_shard_size(1 << 28) == STREAM_SHARD_MIN
    # the benchmark model (~1.3M coords) fits 48 clients in the 256 MB
    # budget: 48 * (4*d + d/8) bytes ~ 250 MB
    assert fedavg.auto_shard_size(1_323_018) == 48
    # always a SIGN_REDUCE_CLIENT_BLK multiple inside the clamp band, so
    # the fp32-weighted fold stays blocked identically across shards
    for d in [1 << 18, 1 << 20, 3_000_000, 10_000_001]:
        k = fedavg.auto_shard_size(d)
        assert k % blk == 0 or k in (STREAM_SHARD_MIN, STREAM_SHARD_MAX)
        assert STREAM_SHARD_MIN <= k <= STREAM_SHARD_MAX


def test_client_keys_invariant_to_partition():
    """client_keys is a counter derivation: any shard partition concatenates
    to the same per-client key rows."""
    key = jax.random.PRNGKey(3)
    whole = np.asarray(Z.client_keys(key, 0, 12))
    parts = np.concatenate([np.asarray(Z.client_keys(key, 0, 5)),
                            np.asarray(Z.client_keys(key, 5, 7))])
    np.testing.assert_array_equal(whole, parts)
    # distinct clients -> distinct keys
    assert len({tuple(r) for r in whole.tolist()}) == 12


# ---------------------------------------------------------------------------
# wire fold API: aggregate(..., acc=...) continues one concatenated reduce
# ---------------------------------------------------------------------------

def test_wire_fold_mask_exact_any_split():
    rng = np.random.RandomState(0)
    packed = jnp.asarray(rng.randint(0, 256, (20, 64)), jnp.uint8)
    mask = jnp.asarray(rng.randint(0, 2, 20).astype(np.float32))
    want = np.asarray(wire.unpack_sum(packed, mask))
    for split in [1, 7, 8, 13]:
        acc = None
        for lo in range(0, 20, split):
            acc = wire.unpack_sum(packed[lo:lo + split], mask[lo:lo + split],
                                  acc=acc)
        np.testing.assert_array_equal(np.asarray(acc), want, err_msg=str(split))
        acc = None
        for lo in range(0, 20, split):
            acc = wire.unpack_sum_mask(packed[lo:lo + split],
                                       mask[lo:lo + split], acc=acc)
        np.testing.assert_array_equal(np.asarray(acc), want, err_msg=str(split))


def test_wire_fold_fp32_weights_exact_at_client_blk_multiples():
    blk = wire.SIGN_REDUCE_CLIENT_BLK
    rng = np.random.RandomState(1)
    packed = jnp.asarray(rng.randint(0, 256, (4 * blk, 128)), jnp.uint8)
    w = jnp.asarray(rng.rand(4 * blk).astype(np.float32))
    want = np.asarray(wire.unpack_sum(packed, w))
    acc = None
    for lo in range(0, 4 * blk, blk):
        acc = wire.unpack_sum(packed[lo:lo + blk], w[lo:lo + blk], acc=acc)
    np.testing.assert_array_equal(np.asarray(acc), want)


@pytest.mark.parametrize("split", [1, 3, 5, 7, 8, 11, 20])
def test_wire_fold_fp32_weights_exact_at_any_split(split):
    """The structured SignFoldAcc carry makes the fp32-weighted fold
    bit-identical to one concatenated reduce at ANY partition — the
    pending-row buffer preserves the full call's 8-client LUT blocking, so
    off-blk splits no longer re-associate the sums. Bytes-level equality
    (tobytes) also pins signed zeros."""
    n, n_bytes = 20, 96
    rng = np.random.RandomState(3)
    packed = jnp.asarray(rng.randint(0, 256, (n, n_bytes)), jnp.uint8)
    w = jnp.asarray(rng.rand(n).astype(np.float32))
    want = np.asarray(wire.unpack_sum(packed, w))
    acc = wire.sign_fold_init(n_bytes)
    for lo in range(0, n, split):
        acc = wire.unpack_sum(packed[lo:lo + split], w[lo:lo + split],
                              acc=acc)
    got = np.asarray(wire.sign_fold_finalize(acc))
    assert got.tobytes() == want.tobytes()


def test_scatter_and_dense_fold():
    rng = np.random.RandomState(2)
    vals = jnp.asarray(rng.randint(-8, 8, (6, 3)).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, 10, (6, 3)))
    m = jnp.asarray(rng.randint(0, 2, 6).astype(np.float32))
    want = np.asarray(wire.scatter_sum_coo(vals, idx, m, 10))
    got = wire.scatter_sum_coo(vals[3:], idx[3:], m[3:], 10,
                               acc=wire.scatter_sum_coo(vals[:3], idx[:3],
                                                        m[:3], 10))
    np.testing.assert_array_equal(np.asarray(got), want)
    dense = jnp.asarray(rng.randint(-4, 4, (6, 10)).astype(np.float32))
    want = np.asarray(wire.dense_masked_sum(dense, m))
    got = wire.dense_masked_sum(dense[3:], m[3:],
                                acc=wire.dense_masked_sum(dense[:3], m[:3]))
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# end-to-end: streaming rounds == vmap rounds
# ---------------------------------------------------------------------------

def _run_rounds(spec, cohort, *, n=16, d=96, rounds=4, seed=5,
                mask=None, glr=0.01, slr=0.3, integer_targets=False,
                jit=True):
    comp = C.Pipeline(spec)
    cfg = fedavg.FedConfig(n_clients=n, client_lr=glr, server_lr=slr)
    ctx = RoundContext(cohort=cohort)
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    step = fedavg.build_round_step(loss_fn, comp, cfg, ctx)
    if jit:  # feed=host returns a Python-loop driver that must not be jitted
        step = jax.jit(step)
    y = jax.random.normal(jax.random.PRNGKey(seed), (1, n, 1, d))
    if integer_targets:
        y = jnp.round(y * 4.0)  # dyadic targets keep every sum associative
    st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                  jax.random.PRNGKey(1))
    mask = jnp.ones((1, n)) if mask is None else mask
    for _ in range(rounds):
        st, m = step(st, {"y": y}, mask)
    return st, m


# 8 of 16 live -> n_live is a power of two, so the post-aggregate mean stays
# dyadic for the integer-target (top-k) case
_MASK16 = jnp.ones((1, 16)).at[0, jnp.asarray([1, 4, 5, 9, 11, 12, 13, 15])
                               ].set(0.0)


@pytest.mark.parametrize("shard", [1, 7, 64])
def test_stream_bit_identical_zsign_packed(shard):
    """0/1 masks -> integer sign sums: streaming at ANY shard size is
    bit-identical to the vmap plan, dead clients included."""
    ref, mref = _run_rounds("zsign_packed(z=1,sigma=0.7)", "vmap",
                            mask=_MASK16)
    got, mgot = _run_rounds("zsign_packed(z=1,sigma=0.7)",
                            f"stream(shard={shard})", mask=_MASK16)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    assert float(mref.loss) == float(mgot.loss)
    assert float(mref.participation) == float(mgot.participation) == 8.0


def test_stream_bit_identical_ef_zsign_at_blk_multiple():
    """EF per-client fp32 scale weights: shard == SIGN_REDUCE_CLIENT_BLK
    continues the same blocked accumulation order -> bit-identical params
    AND residuals."""
    blk = wire.SIGN_REDUCE_CLIENT_BLK
    ref, _ = _run_rounds("ef|zsign", "vmap", mask=_MASK16)
    got, _ = _run_rounds("ef|zsign", f"stream(shard={blk})", mask=_MASK16)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                  np.asarray(got.comp_state["ef"]))


@pytest.mark.parametrize("shard", [1, 7, 64])
def test_stream_bit_identical_ef_zsign_any_shard(shard):
    """EF per-client fp32 scale weights at OFF-blk shard sizes: the
    SignFoldAcc carry keeps the streamed fold in the full call's 8-client
    block order, so streaming is bit-identical to vmap — params AND
    residuals — at every shard size, not just blk multiples."""
    ref, _ = _run_rounds("ef|zsign", "vmap", mask=_MASK16)
    got, _ = _run_rounds("ef|zsign", f"stream(shard={shard})", mask=_MASK16)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                  np.asarray(got.comp_state["ef"]))


@pytest.mark.parametrize("shard", [1, 7, 64])
def test_stream_bit_identical_topk_dyadic(shard):
    """top-k COO scatter sums: dyadic client values (integer targets, dyadic
    lrs, power-of-two live count) make every addition exact, so the
    shard-by-shard scatter fold is bit-identical to the one-shot scatter —
    EF residuals included."""
    kw = dict(mask=_MASK16, glr=0.5, slr=0.5, integer_targets=True)
    ref, _ = _run_rounds("ef|topk(frac=0.25)", "vmap", **kw)
    got, _ = _run_rounds("ef|topk(frac=0.25)", f"stream(shard={shard})", **kw)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                  np.asarray(got.comp_state["ef"]))


@pytest.mark.parametrize("spec", ["zsign", "ef|zsign", "identity", "qsgd",
                                  "ef|topk(frac=0.25)"])
def test_groups_run_as_shards_bit_identical(spec):
    """A (G=3, N=4) cohort under vmap runs as 3 shards of 4 through the
    shard executor: the same 12 clients as (1, 12) under stream(shard=4)
    and stream(shard=1) give the same params and residuals to every bit
    over 3 rounds, dead clients included. Dyadic targets and lrs and a
    power-of-two live count keep the dense and COO sums exact at every
    shard size; sign sums are exact through the SignFoldAcc carry."""
    G, N, d = 3, 4, 32
    y = jnp.round(jax.random.normal(jax.random.PRNGKey(13),
                                    (G * N, 1, d)) * 4.0)
    mask = jnp.ones(G * N).at[jnp.asarray([1, 6, 7, 10])].set(0.0)
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    outs = []
    for groups, n, cohort in [(G, N, "vmap"), (1, G * N, "stream(shard=4)"),
                              (1, G * N, "stream(shard=1)")]:
        comp = C.Pipeline(spec)
        cfg = fedavg.FedConfig(n_clients=n, client_groups=groups,
                               client_lr=0.5, server_lr=0.5)
        step = jax.jit(fedavg.build_round_step(
            loss_fn, comp, cfg, RoundContext(cohort=cohort)))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        for _ in range(3):
            st, m = step(st, {"y": y.reshape(groups, n, 1, d)},
                         mask.reshape(groups, n))
        assert float(m.participation) == 8.0
        state = (None if st.comp_state is None
                 else np.asarray(st.comp_state["ef"]).reshape(G * N, d))
        outs.append((cohort, np.asarray(st.params["x"]), state))
    (_, ref_x, ref_ef), *rest = outs
    for cohort, x, ef in rest:
        assert x.tobytes() == ref_x.tobytes(), cohort
        if ref_ef is not None:
            assert ef.tobytes() == ref_ef.tobytes(), cohort


# ---------------------------------------------------------------------------
# multi-device: shard_map rounds == vmap == single-device stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("devices", [_devices(2), _devices(4), _devices(8)])
@pytest.mark.parametrize("shard", [3, 8])
def test_shard_map_bit_identical_zsign_packed(devices, shard):
    """0/1 masks -> integer sign sums stay exact under the cross-device
    psum, and counter-based keys are placement-invariant: D devices are
    bit-identical to the vmap plan AND the D=1 stream at any shard size,
    multi-round, dead clients included."""
    spec = "zsign_packed(z=1,sigma=0.7)"
    ref, mref = _run_rounds(spec, "vmap", mask=_MASK16)
    one, _ = _run_rounds(spec, f"stream(shard={shard})", mask=_MASK16)
    got, mgot = _run_rounds(spec, f"stream(shard={shard},devices={devices})",
                            mask=_MASK16)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    np.testing.assert_array_equal(np.asarray(one.params["x"]),
                                  np.asarray(got.params["x"]))
    # the loss METRIC is a plain fp32 sum of per-client losses (not part of
    # the integer-exact wire fold), so the psum may re-associate it by an ulp
    assert float(mref.loss) == pytest.approx(float(mgot.loss), rel=1e-6)
    assert float(mref.participation) == float(mgot.participation) == 8.0


@pytest.mark.parametrize("devices", [_devices(2), _devices(4), _devices(8)])
def test_shard_map_ef_zsign_one_round(devices):
    """EF fp32 scale weights across devices: the per-client residuals are
    never summed across devices, so ONE round from the same state leaves
    them bit-identical to the vmap plan (dead clients keep theirs exactly);
    the params go through the psum (a different fp32 association order than
    the sequential fold) and are f32-rounding-close."""
    kw = dict(mask=_MASK16, rounds=1)
    ref, _ = _run_rounds("ef|zsign", "vmap", **kw)
    got, _ = _run_rounds("ef|zsign", f"stream(shard=8,devices={devices})",
                         **kw)
    np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                  np.asarray(got.comp_state["ef"]))
    np.testing.assert_allclose(np.asarray(ref.params["x"]),
                               np.asarray(got.params["x"]), rtol=5e-5,
                               atol=1e-7)


@pytest.mark.parametrize("devices", [_devices(2), _devices(4), _devices(8)])
def test_shard_map_ef_zsign_scale_none_exact_multiround(devices):
    """ef|zsign(scale=none) aggregates with pure 0/1 weights (no per-client
    fp32 scale), so the sharded-residual EF round is FULLY bit-identical
    across device counts over multiple rounds — params and residuals."""
    spec = "ef|zsign(scale=none)"
    ref, _ = _run_rounds(spec, "vmap", mask=_MASK16)
    for cohort in ["stream(shard=8)", f"stream(shard=8,devices={devices})",
                   f"stream(shard=3,devices={devices})"]:
        got, _ = _run_rounds(spec, cohort, mask=_MASK16)
        np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                      np.asarray(got.params["x"]),
                                      err_msg=cohort)
        np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                      np.asarray(got.comp_state["ef"]),
                                      err_msg=cohort)


@pytest.mark.parametrize("devices", [_devices(2), _devices(4), _devices(8)])
def test_shard_map_topk_dyadic_exact(devices):
    """top-k COO scatter across devices: dyadic client values (integer
    targets, dyadic lrs, power-of-two live count) keep every addition —
    including the psum — exact, so shard_map rounds are bit-identical to
    vmap, EF residuals included."""
    kw = dict(mask=_MASK16, glr=0.5, slr=0.5, integer_targets=True)
    ref, _ = _run_rounds("ef|topk(frac=0.25)", "vmap", **kw)
    got, _ = _run_rounds("ef|topk(frac=0.25)",
                         f"stream(shard=3,devices={devices})", **kw)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                  np.asarray(got.comp_state["ef"]))


def test_host_feed_bit_identical_to_device_stream():
    """stream(feed=host): the double-buffered host feeder slices the same
    shards with the same global-index keys and the same left-fold order, so
    the host round is bit-identical to the device-fed stream — residual
    state included. Both run un-jitted here: the host driver cannot be
    jitted, and whole-round jit may fuse the decode/update tail into
    different (ulp-level) fp32 arithmetic than the eager tail, which is a
    jit-vs-eager artifact orthogonal to the shard feeding."""
    spec = "ef|zsign(scale=none)"
    ref, mref = _run_rounds(spec, "stream(shard=5)", mask=_MASK16, rounds=3,
                            jit=False)
    got, mgot = _run_rounds(spec, "stream(shard=5,feed=host)", mask=_MASK16,
                            rounds=3, jit=False)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
    np.testing.assert_array_equal(np.asarray(ref.comp_state["ef"]),
                                  np.asarray(got.comp_state["ef"]))
    assert float(mref.loss) == float(mgot.loss)
    assert int(mgot.shard_clients) == 5


def test_round_metrics_record_shard():
    """RoundMetrics.shard_clients: the resolved (possibly auto-tuned) shard
    size rides out with every streamed round; the vmap plan records 0."""
    _, m = _run_rounds("zsign(z=1,sigma=0.5)", "stream(shard=7)", rounds=1)
    assert int(m.shard_clients) == 7
    _, m = _run_rounds("zsign(z=1,sigma=0.5)", "vmap", rounds=1)
    assert int(m.shard_clients) == 0
    # shard=auto resolves through auto_shard_size (d=96 clamps to the max,
    # then to the cohort size)
    _, m = _run_rounds("zsign(z=1,sigma=0.5)", "stream(shard=auto)", rounds=1)
    assert int(m.shard_clients) == 16


def test_round_metrics_shard_clients_dtype_stable_when_buffered():
    """shard_clients is a DEVICE int32 scalar on every driver path (the
    field default, the jitted stream/vmap rounds, and the eager host-fed
    round), so a buffered metrics window stacks to int32 — a host np.int32
    leaking in would silently re-derive the stacked dtype."""
    default = fedavg.RoundMetrics(*([jnp.zeros(())] * 4)).shard_clients
    assert isinstance(default, jax.Array) and default.dtype == jnp.int32
    buffered = []
    for cohort, jit in [("stream(shard=7)", True), ("vmap", True),
                        ("stream(shard=5,feed=host)", False)]:
        _, m = _run_rounds("zsign(z=1,sigma=0.5)", cohort, rounds=1, jit=jit)
        assert isinstance(m.shard_clients, jax.Array), cohort
        assert m.shard_clients.dtype == jnp.int32, cohort
        buffered.append(m.shard_clients)
    stacked = jnp.stack(buffered + [default])
    assert stacked.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(stacked), [7, 0, 5, 0])


@pytest.mark.parametrize("devices", [_devices(2)])
def test_shard_map_groups_flatten_to_cohort(devices):
    """client_groups > 1 under the device axis: the (G, N) cohort flattens
    to G*N slots before the mesh partition, matching the flat-group run."""
    d = 48
    y = jax.random.normal(jax.random.PRNGKey(11), (2, 4, 1, d))
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    outs = {}
    for groups, n in [(2, 4), (1, 8)]:
        comp = C.Pipeline("zsign(z=1,sigma=0.5)")
        cfg = fedavg.FedConfig(n_clients=n, client_groups=groups,
                               client_lr=0.01, server_lr=0.3)
        step = jax.jit(fedavg.build_round_step(
            loss_fn, comp, cfg,
            RoundContext(cohort=f"stream(shard=3,devices={devices})")))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        st = st._replace(rng=jax.random.PRNGKey(42))
        for _ in range(3):
            st, _ = step(st, {"y": y.reshape(groups, n, 1, d)},
                         jnp.ones((groups, n)))
        outs[groups] = np.asarray(st.params["x"])
    np.testing.assert_array_equal(outs[2], outs[1])


@pytest.mark.parametrize("shard", [1, 7, 64])
def test_shard_size_invariance(shard):
    """Streaming results do not depend on the shard size (counter-based
    keys + associative integer aggregation)."""
    base, _ = _run_rounds("zsign_packed(z=1,sigma=0.7)", "stream(shard=4)",
                          mask=_MASK16)
    got, _ = _run_rounds("zsign_packed(z=1,sigma=0.7)",
                         f"stream(shard={shard})", mask=_MASK16)
    np.testing.assert_array_equal(np.asarray(base.params["x"]),
                                  np.asarray(got.params["x"]))


def test_stream_dead_clients_keep_residual_and_padding_is_inert():
    """A cohort that does not divide the shard (10 clients, shard 4): padded
    slots contribute nothing, dead clients keep residuals bit-exactly, live
    clients update — same as the vmap plan."""
    n, d = 10, 24
    mask0 = jnp.ones((1, n))
    mask = mask0.at[0, 2].set(0.0).at[0, 9].set(0.0)
    outs = {}
    for cohort in ["vmap", "stream(shard=4)"]:
        comp = C.Pipeline("ef|zsign")
        cfg = fedavg.FedConfig(n_clients=n, client_lr=0.01, server_lr=0.3)
        loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
        step = jax.jit(fedavg.build_round_step(loss_fn, comp, cfg,
                                               RoundContext(cohort=cohort)))
        y = jax.random.normal(jax.random.PRNGKey(7), (1, n, 1, d))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        st, _ = step(st, {"y": y}, mask0)       # all-live: residuals nonzero
        before = np.asarray(st.comp_state["ef"]).copy()
        st, m = step(st, {"y": y}, mask)        # kill clients 2 and 9
        assert st.comp_state["ef"].shape == (1, n, d)
        assert float(m.participation) == n - 2
        after = np.asarray(st.comp_state["ef"])
        np.testing.assert_array_equal(after[0, 2], before[0, 2])
        np.testing.assert_array_equal(after[0, 9], before[0, 9])
        for i in range(n):
            if i not in (2, 9):
                assert np.any(after[0, i] != before[0, i]), i
        outs[cohort] = after
    # shard 4 streams 10 clients as 3 shards (2 padded slots); the
    # SignFoldAcc carry keeps the off-blk fp32 scale-weighted fold in full
    # call order -> bit-identical residuals across plans, padding included
    np.testing.assert_array_equal(outs["vmap"], outs["stream(shard=4)"])


def test_stream_groups_flatten_to_cohort():
    """client_groups > 1 under streaming: the (G, N) cohort flattens to
    G*N slots and matches the same clients run as one flat group."""
    d = 48
    y = jax.random.normal(jax.random.PRNGKey(11), (2, 4, 1, d))
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    outs = {}
    for groups, n in [(2, 4), (1, 8)]:
        comp = C.Pipeline("zsign(z=1,sigma=0.5)")
        cfg = fedavg.FedConfig(n_clients=n, client_groups=groups,
                               client_lr=0.01, server_lr=0.3)
        step = jax.jit(fedavg.build_round_step(
            loss_fn, comp, cfg, RoundContext(cohort="stream(shard=3)")))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        st = st._replace(rng=jax.random.PRNGKey(42))
        for _ in range(3):
            st, _ = step(st, {"y": y.reshape(groups, n, 1, d)},
                         jnp.ones((groups, n)))
        outs[groups] = np.asarray(st.params["x"])
    np.testing.assert_array_equal(outs[2], outs[1])


# ---------------------------------------------------------------------------
# memory pins: no full-cohort buffers on the streaming plan
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for vv in (v if isinstance(v, (list, tuple)) else (v,)):
                # ClosedJaxpr carries .jaxpr; shard_map's param is a RAW
                # Jaxpr (has .eqns directly) — recurse into both
                inner = getattr(vv, "jaxpr", vv)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


def _stream_round_jaxpr(n_total, shard, d):
    """A streaming round whose batch leaves are tiny per client, so any
    (n_total, d)-sized array in the jaxpr is a genuine full-cohort gradient
    or payload stack, never input data."""
    comp = C.Pipeline("zsign_packed(z=1,sigma=0.5)")
    cfg = fedavg.FedConfig(n_clients=n_total, client_lr=0.01, server_lr=0.3)
    # d model coords driven by a scalar per-client target
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    step = fedavg.build_round_step(
        loss_fn, comp, cfg, RoundContext(cohort=f"stream(shard={shard})"))
    st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                  jax.random.PRNGKey(1))
    batch = {"y": jnp.zeros((1, n_total, 1, 1))}
    return jax.make_jaxpr(step)(st, batch, jnp.ones((1, n_total)))


def test_stream_jaxpr_has_no_full_cohort_buffers():
    n_total, shard = 64, 8
    d = 2 * C.ENCODE_TILE              # 16384 coords, 2048 wire bytes
    n_bytes = d // 8
    jaxpr = _stream_round_jaxpr(n_total, shard, d)
    scans = [e for e in _walk_eqns(jaxpr.jaxpr)
             if e.primitive.name == "scan"]
    assert scans, "streaming must lower to lax.scan"
    for eqn in _walk_eqns(jaxpr.jaxpr):
        for var in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            shape = tuple(aval.shape)
            if aval.dtype == jnp.float32 and shape[-2:] == (n_total, d):
                raise AssertionError(
                    f"full-cohort (n_total, d) f32 buffer in streaming "
                    f"jaxpr: {eqn}")
            if aval.dtype == jnp.uint8 and len(shape) >= 2 and \
                    shape[-2] == n_total and shape[-1] >= n_bytes:
                raise AssertionError(
                    f"full-cohort uint8 payload stack in streaming "
                    f"jaxpr: {eqn}")


_COLLECTIVES = frozenset({
    "psum", "all_gather", "all_to_all", "ppermute", "pmin", "pmax",
    "reduce_scatter", "pgather", "pbroadcast", "all_gather_invariant"})


@pytest.mark.parametrize("devices", [_devices(2), _devices(4)])
def test_shard_map_only_collective_is_od_psum(devices):
    """The cross-device reduce stays in the compressed-sum domain: the ONLY
    collectives in a stream(devices=D) round jaxpr are fp32 psums of O(d)
    (the wire accumulator) and O(1) (the loss scalar). No all_gather /
    all_to_all / ppermute, no uint8 payload stack and no per-client tensor
    ever crosses the interconnect, so per-device traffic is independent of
    the cohort size."""
    n_total, shard = 32, 4
    d = 2 * C.ENCODE_TILE
    comp = C.Pipeline("zsign_packed(z=1,sigma=0.5)")
    cfg = fedavg.FedConfig(n_clients=n_total, client_lr=0.01, server_lr=0.3)
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    step = fedavg.build_round_step(
        loss_fn, comp, cfg,
        RoundContext(cohort=f"stream(shard={shard},devices={devices})"))
    st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                  jax.random.PRNGKey(1))
    jaxpr = jax.make_jaxpr(step)(st, {"y": jnp.zeros((1, n_total, 1, 1))},
                                 jnp.ones((1, n_total)))
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    assert any(e.primitive.name == "shard_map" for e in eqns)
    colls = [e for e in eqns if e.primitive.name in _COLLECTIVES]
    assert colls, "the device fold must end in a psum"
    for eqn in colls:
        assert eqn.primitive.name == "psum", eqn
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = var.aval
            assert aval.dtype == jnp.float32, eqn
            assert np.prod(aval.shape, dtype=int) <= d, eqn
    jaxpr = None
    for unroll in [1, 2]:
        comp = C.Pipeline("zsign(z=1,sigma=0.5)")
        cfg = fedavg.FedConfig(n_clients=8, client_lr=0.01, server_lr=0.3)
        loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
        step = fedavg.build_round_step(
            loss_fn, comp, cfg,
            RoundContext(cohort=f"stream(shard=2,unroll={unroll})"))
        st = fedavg.init_server_state({"x": jnp.zeros(16)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        jaxpr = jax.make_jaxpr(step)(st, {"y": jnp.zeros((1, 8, 1, 16))},
                                     jnp.ones((1, 8)))
        scans = [e for e in _walk_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "scan"]
        assert scans
        assert any(e.params.get("unroll") == unroll for e in scans), unroll


def test_auto_small_round_compiles_without_scan():
    """cohort=auto (and bare stream) below the element threshold keep the
    scan-free vmap plan — no lax.scan in the round jaxpr at E == 1."""
    for cohort in ["auto", "stream"]:
        comp = C.Pipeline("zsign(z=1,sigma=0.5)")
        cfg = fedavg.FedConfig(n_clients=8, client_lr=0.01, server_lr=0.3)
        loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
        step = fedavg.build_round_step(loss_fn, comp, cfg,
                                       RoundContext(cohort=cohort))
        st = fedavg.init_server_state({"x": jnp.zeros(32)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        jaxpr = jax.make_jaxpr(step)(st, {"y": jnp.zeros((1, 8, 1, 32))},
                                     jnp.ones((1, 8)))
        assert not [e for e in _walk_eqns(jaxpr.jaxpr)
                    if e.primitive.name == "scan"], cohort


# ---------------------------------------------------------------------------
# massive-cohort sampling (fed/sampling.py CohortSampler)
# ---------------------------------------------------------------------------

def test_cohort_sampler_uniform_tier():
    s = CohortSampler(total_clients=100_000, per_round=100, seed=0)
    idx, w = s.sample()
    assert idx.shape == (100,) and w.shape == (100,)
    assert np.all(np.diff(idx) > 0)          # sorted, distinct
    assert np.all(w == 1.0)                  # exact membership mask
    assert 0 <= idx.min() and idx.max() < 100_000


def test_cohort_sampler_importance_weights_debias():
    total, k = 5000, 500
    scores = np.ones(total)
    scores[:100] = 50.0                      # hot clients
    s = CohortSampler(total_clients=total, per_round=k, tier="importance",
                      scores=scores, seed=1)
    idx, w = s.sample()
    assert idx.size == k
    p = scores / scores.sum()
    np.testing.assert_allclose(w, 1.0 / (k * p[idx]), rtol=1e-6)
    # hot clients are much more likely to appear, and carry smaller weights
    hot = (idx < 100).mean()
    assert hot > 0.1
    assert w[idx < 100].mean() < w[idx >= 100].mean()


def test_cohort_sampler_arrival_tier():
    s = CohortSampler(total_clients=20_000, per_round=1, tier="arrival",
                      rate=0.05, seed=2)
    idx, w = s.sample()
    assert 0.03 * 20_000 < idx.size < 0.07 * 20_000
    assert np.all(w == pytest.approx(20.0))  # 1/rate Horvitz-Thompson


def test_cohort_sampler_shard_weights_match_dense():
    s = CohortSampler(total_clients=1000, per_round=64, seed=3)
    idx, w = s.sample()
    dense = s.dense(idx, w, (1, 1000)).reshape(-1)
    rows = list(s.iter_shards(idx, w, shard=64))
    assert len(rows) == -(-1000 // 64)
    got = np.concatenate(rows)[:1000]
    np.testing.assert_array_equal(got, dense)
    # spot-check the binary-search slicing
    np.testing.assert_array_equal(s.shard_weights(idx, w, 3, 64),
                                  dense[3 * 64:4 * 64])


def test_cohort_sampler_device_partitions_match_shard_sequence():
    """device_partitions hands device d the same contiguous slice of the
    global shard sequence the engine's shard_map partition scans there —
    concatenated over devices it is the full (device-padded) sequence."""
    s = CohortSampler(total_clients=1000, per_round=64, seed=4)
    idx, w = s.sample()
    shard, devices = 64, 4
    n_shards = -(-1000 // shard)                       # 16
    padded = -(-n_shards // devices) * devices         # 16
    blocks = list(s.device_partitions(idx, w, shard=shard, devices=devices))
    assert len(blocks) == devices
    assert all(b.shape == (padded // devices, shard) for b in blocks)
    rows = list(s.iter_shards(idx, w, shard=shard))
    rows += [np.zeros(shard, np.float32)] * (padded - len(rows))
    np.testing.assert_array_equal(np.concatenate(blocks), np.stack(rows))
    # uneven: 5 shards over 2 devices pads to 6 (the trailing all-padding
    # shard densifies to a zero row)
    s2 = CohortSampler(total_clients=300, per_round=32, seed=5)
    i2, w2 = s2.sample()
    blocks = list(s2.device_partitions(i2, w2, shard=64, devices=2))
    assert [b.shape for b in blocks] == [(3, 64), (3, 64)]
    np.testing.assert_array_equal(blocks[1][-1], np.zeros(64, np.float32))
    with pytest.raises(ValueError):
        list(s2.device_partitions(i2, w2, shard=64, devices=0))


def test_cohort_sampler_validation():
    with pytest.raises(ValueError):
        CohortSampler(total_clients=10, per_round=11)
    with pytest.raises(ValueError):
        CohortSampler(total_clients=10, per_round=2, tier="nope")
    with pytest.raises(ValueError):
        CohortSampler(total_clients=10, per_round=2, tier="importance")
    with pytest.raises(ValueError):
        CohortSampler(total_clients=10, per_round=2, tier="arrival", rate=0.0)


def test_cohort_sampler_drives_streaming_round():
    """End-to-end: a CohortSampler mask through a streamed round matches the
    same mask through the vmap plan (uniform tier -> exact 0/1 mask)."""
    n = 24
    s = CohortSampler(total_clients=n, per_round=8, seed=9)
    mask = jnp.asarray(s.mask((1, n)))
    assert float(mask.sum()) == 8.0
    ref, _ = _run_rounds("zsign_packed(z=1,sigma=0.7)", "vmap", n=n,
                         mask=mask, rounds=2)
    got, _ = _run_rounds("zsign_packed(z=1,sigma=0.7)", "stream(shard=5)",
                         n=n, mask=mask, rounds=2)
    np.testing.assert_array_equal(np.asarray(ref.params["x"]),
                                  np.asarray(got.params["x"]))
