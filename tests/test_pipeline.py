"""Composable compression-pipeline API: spec grammar, legacy-factory
equivalence, stage composition, RoundContext policy, and the
previously-impossible compositions (dp over the packed 1-bit wire, EF over
top-k).

Contract under test (see core/compression.py):
  * the legacy monolithic class names are factory functions building the
    EQUIVALENT pipeline — bit-exact against the explicit ``Pipeline`` spec
    on encode, compressed-domain aggregate, and decode, including dead-
    client residual semantics (the ``make_compressor`` string entry point
    finished its deprecation cycle and was REMOVED in PR 7);
  * ``ef`` composes over any codec via the one residual rule
    ``codec_input - local_decode(payload)``;
  * a ``dp`` transform's noise FUSES into a downstream sign codec's sigma,
    so DP ships 1 bit/coord with no dense noise surface (jaxpr-enforced);
  * ``RoundContext`` is the one policy object the engine takes.
"""
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypo_compat import given, settings, st
from test_encode_fused import _max_f32_outvar_bytes, _walk_eqns

from repro.core import compression as C
from repro.core import fedavg, wire
from repro.core import noise as Z
from repro.core.context import RoundContext, resolve_backend


# ---------------------------------------------------------------------------
# spec grammar
# ---------------------------------------------------------------------------

def test_spec_parses_stages_and_values():
    p = C.Pipeline("dp(clip=1.5,noise=0.25)|zsign(encode_chunk_tiles=2)")
    assert isinstance(p.transforms[0], C.DPTransform)
    assert p.transforms[0].clip == 1.5
    assert isinstance(p.codec, C.SignCodec)
    assert p.codec.z == 1 and p.codec.encode_chunk_tiles == 2
    assert p.codec.sigma == 0.25          # dp noise fused into the codec
    assert p.name == "dp(clip=1.5,noise=0.25)|zsign(encode_chunk_tiles=2)"
    assert C.Pipeline("zsign(z=inf,sigma=2.0)").codec.z == Z.Z_INF


def test_spec_errors():
    for bad, match in [
            ("", "empty pipeline"),
            ("nope", "unknown codec stage"),
            ("zsign|ef", "unknown transform stage"),   # codec must come last
            ("ef|ef|zsign", "at most one ef"),
            ("zsign(sigma)", "must be key=value"),
            ("zsign(sigma=0.5", "malformed stage"),
            ("zsign(sigma_mode=nope)", "sigma_mode"),
    ]:
        with pytest.raises(ValueError, match=match):
            C.Pipeline(bad)
    with pytest.raises(ValueError, match="ambiguous noise"):
        C.Pipeline("dp(noise=0.5)|zsign(sigma=0.5)")
    with pytest.raises(ValueError, match="clip > 0"):
        C.Pipeline("dp(eps=2.0)|zsign")


def test_state_slots_are_keyed_and_collisions_fail_loudly():
    """The multi-slot state protocol: client state is a keyed dict over the
    stateful stages' declared slots; two stages claiming the same slot name
    is a BUILD-time error, not a silent shared buffer."""
    p = C.Pipeline("ef|zsign")
    st = p.init_state(16)
    assert set(st) == {"ef"} and st["ef"].shape == (16,)
    assert [s.name for s in p.state_slots(16)] == ["ef"]
    assert C.Pipeline("zsign(sigma=0.5)").init_state(16) is None

    class DupState:
        spec_name = "dup"
        stateful = True
        randomized = False

        def state_spec(self, n_coords):
            return (C.StateSlot("ef", (n_coords,)),)

    with pytest.raises(ValueError, match="collision"):
        C.Pipeline((C.ErrorFeedback(), DupState()), C.SignCodec())


def test_spec_roundtrips_through_canonical_string():
    for spec in ["ef|zsign", "dp(clip=1.0,noise=0.5)|zsign_packed",
                 "ef|topk(frac=0.05)", "qsgd(s=4)", "stosign", "identity"]:
        p = C.Pipeline(spec)
        q = C.Pipeline(p.spec)
        assert (q.transforms, q.codec) == (p.transforms, p.codec), spec


def test_ef_sign_scale_convenience_default():
    """ef in front of the NOISE-FREE sign codec defaults the wire to the
    EF-SignSGD mean-abs scale; an explicit scale wins, and noisy z-sign /
    sto-sign keep their own decode laws (no silent hybrid)."""
    assert C.Pipeline("ef|zsign").codec.scale == "mean_abs"
    assert C.Pipeline("ef|zsign(scale=none)").codec.scale == "none"
    assert C.Pipeline("zsign").codec.scale == "none"
    noisy = C.Pipeline("ef|zsign(z=1,sigma=0.5)")
    assert noisy.codec.scale == "none"
    # the Lemma-1 debias survives EF composition over noisy z-sign
    assert float(noisy.decode_mean(jnp.ones(()))) == pytest.approx(
        Z.eta_z(1) * 0.5)
    assert C.Pipeline("ef|stosign").codec.scale == "none"


def test_ef_wire_ignores_dynamic_sigma_like_legacy():
    """The noise-free EF-SignSGD wire ignores the engine's dynamic (Plateau)
    sigma, exactly as the legacy EFSignCompressor did (del sigma): payload
    bits stay noise-free and bit-identical with or without the override."""
    d = 64
    p = C.Pipeline("ef|zsign")
    flat = jnp.asarray(np.random.RandomState(0).randn(d), jnp.float32)
    key = jax.random.PRNGKey(0)
    e0, s0 = p.encode(key, flat, p.init_state(d))
    e1, s1 = p.encode(key, flat, p.init_state(d), sigma=jnp.float32(0.7))
    np.testing.assert_array_equal(np.asarray(e0["packed"]),
                                  np.asarray(e1["packed"]))
    np.testing.assert_array_equal(np.asarray(s0["ef"]), np.asarray(s1["ef"]))


def test_dp_fusion_requires_gaussian_sign_codec():
    """The dp accountant assumes the Gaussian mechanism: fusing into a
    z != 1 (e.g. bounded-uniform z=inf) or norm-mode sign codec would void
    the calibrated (eps, delta) guarantee and must refuse."""
    for bad in ["dp(clip=1.0,noise=0.5)|zsign(z=inf)",
                "dp(clip=1.0,noise=0.5)|stosign"]:
        with pytest.raises(ValueError, match="Gaussian"):
            C.Pipeline(bad)


def test_dynamic_sigma_refused_over_calibrated_dp_stage():
    """The Plateau override may not replace (eps, delta)-CALIBRATED dp noise
    — neither on the fused 1-bit pipeline nor on dp|dense. A hand-set
    dp(noise=..) carries no privacy promise and keeps the legacy dpgauss
    law: the dynamic sigma overrides it."""
    for spec in ["dp(clip=1.0,eps=2.0,steps=100)|zsign",
                 "dp(clip=1.0,eps=2.0,steps=100)|dense"]:
        p = C.Pipeline(spec)
        assert p.transforms[0].calibrated
        with pytest.raises(ValueError, match="Plateau"):
            p.with_context(RoundContext(dynamic_sigma=True))
        # and through the engine entry point
        with pytest.raises(ValueError, match="Plateau"):
            fedavg.build_round_step(lambda pr, b: 0.0, p, fedavg.FedConfig(),
                                    RoundContext(dynamic_sigma=True))
    # legacy dpgauss + Plateau still builds and consumes the dynamic sigma
    legacy = C.DPGaussianCompressor(sigma=0.3)
    step = fedavg.build_round_step(
        lambda pr, b: 0.5 * jnp.sum((pr["x"] - b["y"]) ** 2), legacy,
        fedavg.FedConfig(n_clients=2, client_lr=0.01),
        RoundContext(dynamic_sigma=True))
    st = fedavg.init_server_state({"x": jnp.zeros(8)}, fedavg.FedConfig(
        n_clients=2, client_lr=0.01), legacy, jax.random.PRNGKey(0),
        sigma0=0.7)
    st2, _ = jax.jit(step)(st, {"y": jnp.ones((1, 2, 1, 8))},
                           jnp.ones((1, 2)))
    assert np.all(np.isfinite(np.asarray(st2.params["x"])))


def test_dp_eps_and_noise_together_raise():
    with pytest.raises(ValueError, match="not.*both|one target"):
        C.Pipeline("dp(clip=1.0,eps=2.0,noise=0.3)|zsign")


def test_clip_only_dp_never_consumes_dynamic_sigma():
    """dp(clip=...) with NO noise over a dense codec: a dynamic sigma passed
    directly to encode must not inject noise into a noise-free pipeline."""
    p = C.Pipeline("dp(clip=1.0)|dense")
    flat = 10.0 * jnp.ones((32,))
    got, _ = p.encode(jax.random.PRNGKey(0), flat, None,
                      sigma=jnp.float32(0.5))
    from repro.core.dp import clip_flat
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(clip_flat(flat, 1.0)))


def test_fractional_z_rejected():
    with pytest.raises(ValueError, match="integer or 'inf'"):
        C.Pipeline("zsign(z=2.5)")


def test_legacy_factories_reject_unknown_kwargs():
    """A typo'd hyper-parameter must fail loudly, as the old dataclass
    constructors did — never run the experiment with silent defaults."""
    with pytest.raises(TypeError):
        C.QSGDCompressor(sigma=0.5)
    with pytest.raises(TypeError):
        C.TopKCompressor(sigma=0.5)
    with pytest.raises(TypeError):
        C.DPGaussianCompressor(frac=0.1)
    with pytest.raises(TypeError):
        C.ZSignCompressor(frac=0.5)   # SignCodec has no such field


def test_spec_sigma_is_explicit_vanilla_sign_by_default():
    """The sigma=None optionality wart is gone: sigma is a plain float field,
    0.0 by default (= vanilla SignSGD, PRNG statically gated off)."""
    p = C.Pipeline("zsign")
    assert p.codec.sigma == 0.0
    flat = jnp.asarray([-2.0, -0.1, 0.0, 0.1, 3.0], jnp.float32)
    enc, _ = p.encode(jax.random.PRNGKey(0), flat, None)
    np.testing.assert_array_equal(
        np.asarray(wire.unpack_signs(enc))[:5],
        np.array([-1, -1, 1, 1, 1], np.int8))


def test_packed_sigma_zero_noprng_jaxpr_pinned():
    """Regression pin (satellite): the packed sign codec at sigma == 0 keeps
    its no-PRNG jaxpr guarantee under the new API, on every backend."""
    flat = jnp.ones((8192,))
    for backend in ["reference", "jnp", "pallas"]:
        p = C.Pipeline(f"zsign_packed(encode_backend={backend})")
        assert p.codec.sigma == 0.0
        jaxpr = jax.make_jaxpr(lambda k, f: p.encode(k, f, None)[0])(
            jax.random.PRNGKey(0), flat)
        for eqn in _walk_eqns(jaxpr.jaxpr):
            assert "threefry" not in eqn.primitive.name, (backend, eqn)
            assert "erf" not in eqn.primitive.name, (backend, eqn)


# ---------------------------------------------------------------------------
# legacy-factory equivalence: factory class name == explicit Pipeline spec,
# bit-exact (the make_compressor string shim is gone — see its removal test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("factory,kw,spec", [
    (C.ZSignCompressor, {"z": 1, "sigma": 0.5}, "zsign(z=1,sigma=0.5)"),
    (C.ZSignCompressor, {"z": 0, "sigma": 2.0}, "zsign(z=inf,sigma=2.0)"),
    (C.PackedZSignCompressor, {"z": 1, "sigma": 0.5},
     "zsign_packed(sigma=0.5)"),
    (C.StoSignCompressor, {}, "stosign"),
    (C.EFSignCompressor, {}, "ef|zsign"),
    (C.QSGDCompressor, {"s": 2}, "qsgd(s=2)"),
    (C.TopKCompressor, {"frac": 0.25}, "ef|topk(frac=0.25)"),
    (C.DPGaussianCompressor, {"sigma": 0.3}, "dp(noise=0.3)|dense"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_factory_encode_aggregate_decode_bit_exact(factory, kw, spec):
    """Legacy factory name vs the explicit spec string: identical payload
    bytes/values, identical masked aggregate, identical decode."""
    d, n = 1000, 4
    legacy = factory(**kw)
    pipe = C.Pipeline(spec)
    flat = jnp.asarray(np.random.RandomState(0).randn(d), jnp.float32)
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    encs, states = {}, {}
    for label, comp in [("legacy", legacy), ("spec", pipe)]:
        st0 = comp.init_state(d)
        es, ss = [], []
        for i in range(n):
            e, s = comp.encode(jax.random.fold_in(jax.random.PRNGKey(7), i),
                               flat * (i + 1), st0)
            es.append(e)
            ss.append(s)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *es)
        agg = comp.aggregate(stacked, mask, d)
        dec = comp.decode_mean(agg / jnp.sum(mask))
        encs[label] = (es, agg, dec)
        states[label] = ss
    for a, b in zip(jax.tree_util.tree_leaves(encs["legacy"]),
                    jax.tree_util.tree_leaves(encs["spec"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(states["legacy"]),
                    jax.tree_util.tree_leaves(states["spec"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("groups", [1, 2])
def test_efsign_factory_vs_ef_zsign_engine_bit_identical(groups):
    """EFSignCompressor() vs Pipeline("ef|zsign") through the ROUND
    ENGINE under partial participation: bit-identical params AND residuals
    every round (dead clients keep their residual bit-exactly on both)."""
    d, n = 48, 4
    y = jax.random.normal(jax.random.PRNGKey(2), (groups, n, 1, d))
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    cfg = fedavg.FedConfig(n_clients=n, client_groups=groups,
                           client_lr=0.01, server_lr=0.5)
    mask = jnp.ones((groups, n)).at[0, 1].set(0.0).at[groups - 1, 3].set(0.0)
    outs = {}
    for label, comp in [("legacy", C.EFSignCompressor()),
                        ("spec", C.Pipeline("ef|zsign"))]:
        step = jax.jit(fedavg.build_round_step(loss_fn, comp, cfg))
        st = fedavg.init_server_state({"x": jnp.zeros(d)}, cfg, comp,
                                      jax.random.PRNGKey(1))
        for _ in range(6):
            st, _ = step(st, {"y": y}, mask)
        outs[label] = (np.asarray(st.params["x"]),
                       np.asarray(st.comp_state["ef"]))
    np.testing.assert_array_equal(outs["legacy"][0], outs["spec"][0])
    np.testing.assert_array_equal(outs["legacy"][1], outs["spec"][1])
    # dead clients' residuals froze after round 1 only if masked — sanity:
    assert outs["legacy"][1].shape == (groups, n, d)


# ---------------------------------------------------------------------------
# ef over top-k: residual correctness
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=8, max_value=400),
       st.integers(min_value=1, max_value=97))
def test_ef_topk_residual_conservation_property(d, seed):
    """EF invariant over the COO codec, any shape: transmitted + residual
    == codec input EXACTLY (p[idx] - p[idx] == 0 in f32), and the residual
    is zero exactly on the selected coordinates."""
    rng = np.random.RandomState(seed)
    p = C.Pipeline("ef|topk(frac=0.2)")
    state = {"ef": jnp.asarray(rng.randn(d), jnp.float32) * 0.1}
    flat = jnp.asarray(rng.randn(d), jnp.float32)
    enc, res = p.encode(None, flat, state)
    dense = np.zeros(d, np.float32)
    dense[np.asarray(enc["indices"])] = np.asarray(enc["values"])
    np.testing.assert_array_equal(dense + np.asarray(res["ef"]),
                                  np.asarray(flat + state["ef"]))
    assert np.all(np.asarray(res["ef"])[np.asarray(enc["indices"])] == 0.0)


def test_ef_topk_error_feedback_contracts():
    """EF over top-k compensates: the running decoded average of a constant
    gradient converges to the gradient even at frac=0.25."""
    p = C.Pipeline("ef|topk(frac=0.25)")
    flat = jnp.asarray([1.0, -0.2, 0.05, 3.0])
    state = p.init_state(4)
    dec_sum = np.zeros(4)
    T = 200
    for i in range(T):
        enc, state = p.encode(None, flat, state)
        dec_sum += np.asarray(
            p.aggregate(jax.tree.map(lambda x: x[None], enc),
                        jnp.ones((1,)), 4))
    np.testing.assert_allclose(dec_sum / T, np.asarray(flat), atol=0.05)


def test_ef_composes_over_qsgd():
    """EF over the quantizer: residual == p - quantized, by the one rule."""
    d = 64
    p = C.Pipeline("ef|qsgd(s=1)")
    flat = jnp.asarray(np.random.RandomState(0).randn(d), jnp.float32)
    enc, res = p.encode(jax.random.PRNGKey(3), flat, p.init_state(d))
    np.testing.assert_allclose(np.asarray(enc) + np.asarray(res["ef"]),
                               np.asarray(flat), atol=1e-6)


# ---------------------------------------------------------------------------
# dp composition: fusion into the sign codec, 1-bit wire, no dense surface
# ---------------------------------------------------------------------------

def test_dp_noise_fuses_into_sign_codec_bit_exact():
    """dp(clip,noise)|zsign == clip, then the SAME fused stochastic-sign
    encode a bare zsign(sigma=noise) codec runs — bit-identical wire bytes
    for the same key."""
    d, clipn, sig = 3 * 8192 + 17, 1.0, 0.5
    key = jax.random.PRNGKey(11)
    flat = 3.0 * jax.random.normal(jax.random.PRNGKey(1), (d,))
    fused = C.Pipeline(f"dp(clip={clipn},noise={sig})|zsign")
    assert fused.codec.sigma == sig and fused.transforms[0].noise == 0.0
    got, _ = fused.encode(key, flat, None)
    from repro.core.dp import clip_flat
    want, _ = C.Pipeline(f"zsign(sigma={sig})").encode(
        key, clip_flat(flat, clipn), None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dp_eps_calibration_monotone():
    tight = C.Pipeline("dp(clip=1.0,eps=1.0,steps=100)|zsign")
    loose = C.Pipeline("dp(clip=1.0,eps=8.0,steps=100)|zsign")
    assert tight.codec.sigma > loose.codec.sigma > 0.0
    assert tight.wire_bits_per_coord == 1.0


def test_dp_over_dense_is_legacy_dpgauss_plus_clip():
    """Over a dense codec the dp noise stays in the transform (32-bit DP-
    FedAvg); clip applies before the draw."""
    d = 256
    key = jax.random.PRNGKey(5)
    flat = 10.0 * jnp.ones((d,))
    p = C.Pipeline("dp(clip=1.0,noise=0.3)|dense")
    assert p.transforms[0].noise == 0.3       # NOT fused
    got, _ = p.encode(key, flat, None)
    from repro.core.dp import clip_flat
    want = clip_flat(flat, 1.0) + 0.3 * jax.random.normal(key, (d,))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dp_packed_composition_trains_and_has_no_dense_noise_surface():
    """The previously-impossible dp|zsign_packed: trains end-to-end on the
    consensus problem at 1 bit/coord, and the vmapped client fan-out jaxpr
    contains NO jax.random draw (threefry2x32 primitive) and no fp32
    intermediate beyond the 1x (n_clients, d) transform stream — the dense
    noise surface is NOT reintroduced."""
    pipe = C.Pipeline("dp(clip=2.0,noise=1.0)|zsign_packed")
    assert pipe.wire_bits_per_coord == 1.0
    # jaxpr enforcement on the client fan-out
    n, d = 16, 2 * 8192 + 100
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    flats = jnp.zeros((n, d))
    jaxpr = jax.make_jaxpr(
        jax.vmap(lambda k, f: pipe.encode(k, f, None)[0]))(keys, flats)
    for eqn in _walk_eqns(jaxpr.jaxpr):
        assert eqn.primitive.name != "threefry2x32", eqn
    worst = _max_f32_outvar_bytes(jaxpr.jaxpr)
    assert worst <= 4 * n * d, worst      # <= one clipped-gradient surface
    # end-to-end: converges toward the (noisy) consensus optimum
    dcons, ncl = 50, 8
    y = jax.random.normal(jax.random.PRNGKey(3), (1, ncl, 1, dcons))
    loss_fn = lambda p, b: 0.5 * jnp.sum((p["x"] - b["y"]) ** 2)
    cfg = fedavg.FedConfig(n_clients=ncl, client_lr=0.01, server_lr=1.0)
    step = jax.jit(fedavg.build_round_step(loss_fn, pipe, cfg))
    st = fedavg.init_server_state({"x": jnp.zeros(dcons)}, cfg, pipe,
                                  jax.random.PRNGKey(1))
    d0 = float(jnp.linalg.norm(st.params["x"] - y[0, :, 0].mean(0)))
    for _ in range(400):
        st, m = step(st, {"y": y}, jnp.ones((1, ncl)))
    d1 = float(jnp.linalg.norm(st.params["x"] - y[0, :, 0].mean(0)))
    assert d1 < 0.5 * d0
    assert float(m.uplink_bits) == ncl * dcons  # 1 bit/coord on the wire


def test_ef_topk_no_dense_aggregate_surface():
    """EF over top-k: the server aggregation jaxpr scatter-adds COO payloads
    — no (n_clients, d) fp32 dense per-client surface appears."""
    n, d, k = 16, 100_000, 1000
    pipe = C.Pipeline("ef|topk(frac=0.01)")
    payload = {"values": jnp.zeros((n, k)),
               "indices": jnp.zeros((n, k), jnp.int32)}
    jaxpr = jax.make_jaxpr(
        lambda p, m: pipe.aggregate(p, m, d))(payload, jnp.ones((n,)))
    for eqn in _walk_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            assert int(np.prod(aval.shape, dtype=np.int64)) < n * d, eqn


@pytest.mark.parametrize("spec", ["dp(clip=1.0,noise=0.1)|zsign_packed",
                                  "ef|topk(frac=0.05)"])
def test_cli_trains_pipeline_spec_end_to_end(spec):
    """The train CLI accepts --pipeline spec strings for the previously-
    impossible compositions and completes rounds."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2_0_5b",
         "--reduced", "--rounds", "1", "--clients", "2", "--local-steps",
         "1", "--seq-len", "32", "--micro-batch", "1", "--pipeline", spec],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(
            __file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"compressor={spec}" in out.stdout
    assert "# done: 1 rounds" in out.stdout


# ---------------------------------------------------------------------------
# RoundContext policy
# ---------------------------------------------------------------------------

def test_with_context_per_stage_rebinding():
    ctx = RoundContext(agg_backend="dense", encode_backend="reference",
                       weights_are_mask=True)
    # sign codec: both backends rebound, mask guarantee applied
    p = C.Pipeline("zsign(sigma=0.5)").with_context(ctx)
    assert p.codec.agg_backend == "dense"
    assert p.codec.encode_backend == "reference"
    assert p.codec.weights_are_mask
    # None backends keep the stage's own pin (zsign_packed stays pallas)
    q = C.Pipeline("zsign_packed").with_context(RoundContext())
    assert q.codec.encode_backend == "pallas"
    # scale-weighted (EF) aggregation never gets the 0/1-mask flag: its
    # weights are mask * scale, not a membership mask
    e = C.Pipeline("ef|zsign").with_context(ctx)
    assert not e.codec.weights_are_mask
    # non-sign codecs have no backend fields to rebind
    t = C.Pipeline("ef|topk")
    assert t.with_context(ctx) is t


def test_round_context_and_backend_validation():
    with pytest.raises(ValueError, match="unknown agg backend"):
        RoundContext(agg_backend="nope")
    with pytest.raises(ValueError, match="unknown encode backend"):
        RoundContext(encode_backend="dense")
    with pytest.raises(ValueError, match="unknown agg backend"):
        resolve_backend("agg", "reference")
    assert resolve_backend("agg", "auto") in ("jnp", "pallas")
    assert resolve_backend("encode", "reference") == "reference"


# ---------------------------------------------------------------------------
# deprecation shim: removed in PR 7
# ---------------------------------------------------------------------------

def test_make_compressor_shim_is_gone_and_api_warning_free():
    """The make_compressor(name) string entry point finished its deprecation
    cycle: the attribute no longer exists (no half-removed stub), and the
    surviving API — Pipeline specs and the legacy factory names — emits no
    DeprecationWarning."""
    assert not hasattr(C, "make_compressor")
    assert "make_compressor" not in C.__all__
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        C.Pipeline("ef|zsign")
        C.ZSignCompressor(sigma=0.5)
        C.EFSignCompressor()
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]


def test_pipeline_is_hashable_and_fields_clean():
    """Frozen dataclass: usable as a static jit closure; the engine-visible
    fields are exactly (transforms, codec, name) — per-stage knobs like
    weights_are_mask live on stages, not the pipeline."""
    import dataclasses
    p = C.Pipeline("ef|zsign")
    assert hash(p) == hash(C.Pipeline("ef|zsign"))
    assert {f.name for f in dataclasses.fields(p)} == \
        {"transforms", "codec", "name"}
