"""Composable compression pipelines over the flat wire-buffer codec (wire.py).

The paper's central claim is that stochastic sign compression is ONE scheme
with many instances (sto-sign is the z -> inf member, DP mechanisms compose
with sign transmission, error feedback wraps any contractive codec). This
module makes the code shape match the math shape: a compressor is a
:class:`Pipeline` of orthogonal stages rather than a bespoke class per
combination.

Stage taxonomy
--------------

``Transform`` stages are codec-agnostic pre-processing on the flat fp32
buffer, applied client-side before anything touches the wire:

  ``ef``              error-feedback residual (Karimireddy et al. '19): adds
                      the per-client residual before the codec and records
                      what the codec failed to transmit. Stateful: one flat
                      fp32 slot ("ef") per client.
  ``cv``              compressed SCAFFOLD control variates (SCALLION,
                      arXiv:2308.08165): per-client variate c_i (slot "cv")
                      plus a SHARED server variate c (server-scope slot
                      "cv_server"); pre-codec drift correction
                      p - eta*(c_i - c), variate updates from the locally
                      decoded payload — the heterogeneity fix at ZERO extra
                      wire cost. ``cv|zsign_packed`` is compressed SCAFFOLD
                      at 1 bit/coord.
  ``sigma_sched``     per-layer sigma schedule (paper §5 "layer-wise sigma"):
                      a STATIC geometric ramp of per-leaf multipliers m_j
                      from ``head`` to ``tail`` applied to the flat buffer
                      before the codec. For sign codecs
                      Sign(m_j*p + sigma*xi) == Sign(p + (sigma/m_j)*xi), so
                      scaling the buffer IS running layer j at effective
                      noise sigma/m_j — one scalar codec sigma, per-layer
                      effect. Stateless; the server decode divides the
                      estimate by m. Needs the round's TreeSpec
                      (``needs_tree_spec``) to map leaves to coordinate
                      ranges; must be the first stage and cannot compose
                      with ``cv``.
  ``dp``              DP clip + Gaussian noise (paper Algorithm 2): clips the
                      buffer to norm ``clip`` and adds ``noise`` * N(0, I).
                      When the pipeline's codec is a sign codec the noise is
                      FUSED into the codec's sigma (the same Gaussian does
                      double duty: privacy and the Lemma-1 sign-bias
                      correction), so the dense noise buffer never exists and
                      the wire stays 1 bit/coord. ``dp(clip=1.0,eps=2.0)``
                      calibrates the noise from a target (eps, delta) via the
                      RDP accountant in core/dp.py.

``WireCodec`` stages own the :class:`~repro.core.wire.WireFormat` and BOTH
ends of the wire: the client encode and the server's compressed-domain
``aggregate`` (sign codecs reduce bitpacked payloads through
:func:`sign_reduce` without ever materializing the dense (n_clients, d) sign
matrix; the COO codec scatter-adds):

  ``zsign``           the paper's stochastic sign operator: bitpacked
                      Sign(x + sigma * xi_z) at 1 bit/coord, counter-based
                      fused encode for z in {inf, 1}. ``sigma`` is an
                      EXPLICIT field (default 0.0 = vanilla SignSGD, which
                      statically gates off the PRNG on every backend);
                      ``sigma_mode="norm"`` is the sto-sign instance
                      (sigma_i = ||flat_i||), ``scale="mean_abs"`` transmits
                      the EF-SignSGD per-client magnitude next to the bits.
  ``zsign_packed``    same codec pinned to the Pallas TPU kernels (in-kernel
                      counter noise; dense-reference path through
                      ``zsign_compress``). sigma == 0 keeps the no-PRNG
                      jaxpr guarantee (regression-pinned in tests).
  ``stosign``         alias: ``zsign(sigma_mode=norm, z=inf)``.
  ``qsgd``            unbiased stochastic quantizer (Alistarh et al.),
                      dense fp32 wire of ceil(log2(2s+1)) logical bits/coord.
  ``topk``            global top-k sparsifier, COO (values, indices) wire;
                      STATELESS — compose ``ef|topk`` for the classic
                      residual-corrected variant.
  ``identity``/``dense``  uncompressed fp32 FedAvg.

A :class:`Pipeline` is transforms + one codec, buildable from a spec string:

    Pipeline("ef|zsign")                        # == EF-SignSGD, bit-exact
    Pipeline("zsign(z=1,sigma=0.5)")            # the paper's 1-SignFedAvg
    Pipeline("dp(clip=1.0,eps=2.0)|zsign_packed")  # DP at 1 bit/coord
    Pipeline("ef|topk(frac=0.01)")              # EF over sparsification

and exposes the engine-facing compressor interface (core/fedavg.py consumes
it unchanged):

    init_state(n_coords)              -> keyed per-client state dict
                                         ({slot_name: buffer}) or None
    init_server_state(n_coords)       -> keyed SHARED server state dict
                                         (control variates) or None
    encode(key, flat, state, sigma,
           server, spec)             -> (payload, new_state)  # client
    update_server(server, g_dec,
                  n_live, n_total)    -> new server state       # round tail
    aggregate(payload, mask, n_coords)-> masked SUM accumulator   # server
                                         ((d_pad,) f32, or the (2, d_pad)
                                         int32 vote pair for robust agg=)
    decode_sum(enc_sum, n_live,
               sigma, spec)          -> (d_pad,) f32 estimate    # server
    decode_mean(flat_mean, sigma,
                spec)                -> (d_pad,) f32 estimate (mean law)
    wire_format()                     -> WireFormat (dtype, bits/coord, ...)

``flat`` is the pseudo-gradient flattened ONCE by the engine
(wire.TreeSpec); ``spec`` is that TreeSpec, passed exactly when the
pipeline declares ``needs_tree_spec`` (sigma_sched); ``payload`` is what
crosses the network. ``aggregate``
consumes payloads stacked on a leading client axis with the (n_clients,)
participation mask; all decoders are linear in the per-client encodings, so
group-sum aggregation across sequential client groups is exact.

State composition contract: every STATEFUL stage declares named slots
through ``state_spec(n_coords)`` (``fed/client_state.StateSlot``); the
pipeline's client state is the keyed dict ``{slot_name: buffer}`` and slot
names must be unique across stages (collision -> build-time error). A
stateful stage participates in ``encode`` through two hooks:
``pre_encode(key, p, state, sigma, server)`` maps the buffer forward and
``post_encode(state, codec_input, local_decode, server)`` returns its
updated slots, where ``local_decode`` is the exact per-client value the
server will attribute to this payload (scale * signs for the sign codec,
the scattered values for top-k, the quantized levels for qsgd) and
``server`` is the shared server-scope tree (None unless a stage declares
server slots). A stage owning server slots may add an
``update_server(server, g_dec, n_live, n_total)`` hook, run once per round
by the engine's finish step on the DECODED aggregate.

Error-feedback is the canonical instance: ``ef`` adds its residual slot to
the buffer it receives; after the codec runs, the new residual is
``codec_input - local_decode(payload)``. That one rule reproduces
EF-SignSGD and EF-top-k bit-exactly and makes EF work over every codec.

Backend policy lives in core/context.py: ``RoundContext`` carries the
deployment's ``agg_backend`` / ``encode_backend`` / mask guarantee, and
``resolve_backend`` is the one place "auto" becomes pallas-on-TPU /
jnp-elsewhere. ``Pipeline.with_context(ctx)`` rebinds every sign stage —
kernels are dispatched per-stage, not per-class.

The legacy monolithic class names survive as factory functions building the
equivalent pipeline (``EFSignCompressor()`` == ``Pipeline("ef|zsign")``, bit
for bit — pinned in tests/test_pipeline.py); the ``make_compressor(name)``
string entry point was removed in PR 7 after its deprecation cycle — build
a ``Pipeline("<spec>")`` instead (docs/API.md has the migration table).
Fused encode/reduce internals (``fused_sign_encode_jnp``, ``sign_reduce``,
wire-size accounting) are unchanged from the pre-pipeline module — see
wire.py for the accounting notes and kernels/zsign for the TPU paths.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import noise as znoise
from repro.core import wire
from repro.core.context import (AGG_BACKENDS, ENCODE_BACKENDS, RoundContext,
                                resolve_backend)
from repro.core.wire import (WireFormat, pack_flat, pack_signs,
                             unpack_signs, unpack_sum)
# dependency-free substrate module (jax-only): no core <-> fed cycle
from repro.fed import client_state as cstate_lib
from repro.fed.client_state import StateSlot

__all__ = [
    "Pipeline", "SignCodec", "QSGDCodec", "TopKCodec", "DenseCodec",
    "ErrorFeedback", "DPTransform", "ControlVariate", "SigmaSchedule",
    "RoundContext", "StateSlot",
    "Compressor", "ZSignCompressor", "StoSignCompressor", "EFSignCompressor",
    "QSGDCompressor", "TopKCompressor", "DPGaussianCompressor",
    "PackedZSignCompressor", "available", "global_norm",
    "pack_signs", "unpack_signs", "sign_reduce", "fused_sign_encode_jnp",
    "AGG_BACKENDS", "ENCODE_BACKENDS",
]

#: fused-encode tile, in elements. MUST equal kernels/zsign ops.TILE — the
#: jnp fallback reproduces the kernel's per-tile counter stream (asserted in
#: tests without importing the Pallas stack here).
ENCODE_TILE = 8192


def fused_sign_encode_jnp(flat: jax.Array, key, sigma, *, z: int,
                          add_noise: bool = True,
                          chunk_tiles: int = 0) -> jax.Array:
    """Counter-based fused encode, pure jnp — bit-exact vs the Pallas kernel.

    (d,) f32 -> tile-padded bitpacked uint8 (ceil(d/8192)*1024 bytes), the
    identical byte stream ``kernels/zsign ops.zsign_encode_fused`` produces
    for the same key (same global element counters, same per-tile word
    layout, same f32 threshold math — see noise.tile_u01 /
    noise.stochastic_sign_bits).

    ``chunk_tiles == 0`` (default): one elementwise pass. Each quarter-
    counter's threefry call yields the bits of four coordinates (one per
    tile quarter); they are packed into one 4-bit code per counter, and the
    wire bytes are read off the code's bit planes. So the largest computed
    intermediate is the (d/4,) uint8 code, never a (d,) f32 uniform: XLA
    fuses elementwise chains but will not fuse a concatenation of the four
    quarter streams, which would otherwise hold two (d,) f32 buffers. That
    holds on the CPU compiler only (pinned by tests/test_encode_fused.py):
    the TPU compiler does not fuse the pass, and at qwen2-0.5B width
    (d = 494M) it needs 7.8 GiB of temp.

    ``chunk_tiles > 0``: lax.scan over chunks of that many 8192-element
    tiles, bounding the noise window to one chunk per client whatever the
    compiler fuses — use it at LM widths on the TPU (3.7 GiB of temp at
    d = 494M with 64-tile chunks: two copies of the padded input). The
    scan carries ~30-80ms of loop overhead per round on small CPUs, so it
    is opt-in rather than the default.
    """
    d = flat.shape[0]
    tile = ENCODE_TILE
    q = tile // 4
    n_tiles = -(-d // tile)
    dpad = n_tiles * tile
    flat = flat.astype(jnp.float32)
    if not add_noise:
        return pack_flat(jnp.pad(flat, (0, dpad - d)))
    k0, k1 = znoise.key_words(key)
    sig = jnp.asarray(sigma, jnp.float32)
    inv = znoise.threshold_scale(sig, z)

    def tiles_packed(x_chunk, first_tile, n):
        # quarter j of tile t holds elements t*tile + j*q + k, and takes its
        # uniforms from half j of the words of counters t*q + k
        x4 = x_chunk.reshape(n, 4, q)
        cnt = ((first_tile + jnp.arange(n, dtype=jnp.uint32))[:, None]
               * jnp.uint32(q) + jnp.arange(q, dtype=jnp.uint32)[None])
        y0, y1 = znoise.counter_words(k0, k1, cnt)
        us = znoise.halves_to_u01(y0) + znoise.halves_to_u01(y1)
        code = jnp.zeros((n, q), jnp.uint8)
        for j, u in enumerate(us):
            x = x4[:, j]
            bit = jnp.where(sig > 0, znoise.noisy_sign_bits(x, u, inv, z),
                            x >= 0)
            code = code | (bit.astype(jnp.uint8) << j)
        planes = (code[:, None, :] >> jnp.arange(4, dtype=jnp.uint8)[:, None]
                  ) & jnp.uint8(1)                        # (n, 4, q)
        return wire.pack_bool(planes > 0)

    if chunk_tiles <= 0 or n_tiles <= chunk_tiles:
        # whole tiles straight from the input, only the last one padded: a
        # pad of the whole buffer would be a (d,) copy
        n_full = d // tile
        parts = [tiles_packed(flat[:n_full * tile], jnp.uint32(0), n_full)]
        if dpad > d:
            tail = jnp.pad(flat[n_full * tile:], (0, dpad - d))
            parts.append(tiles_packed(tail, jnp.uint32(n_full), 1))
        return jnp.concatenate(parts)
    n_chunks = -(-n_tiles // chunk_tiles)
    x2 = jnp.pad(flat, (0, n_chunks * chunk_tiles * tile - d)).reshape(
        n_chunks, chunk_tiles * tile)
    starts = jnp.arange(n_chunks, dtype=jnp.uint32) * jnp.uint32(chunk_tiles)
    _, packed = jax.lax.scan(
        lambda _, xs: (None, tiles_packed(xs[0], xs[1], chunk_tiles)),
        None, (x2, starts))
    return packed.reshape(-1)[: dpad // 8]


def sign_reduce(packed: jax.Array, weights: jax.Array,
                backend: str = "auto", *,
                weights_are_mask: bool = False,
                acc: jax.Array | None = None,
                debug: bool = False) -> jax.Array:
    """Weighted sign-reduce over stacked bitpacked payloads.

    (n_clients, n_bytes) u8 + (n_clients,) f32 -> (8*n_bytes,) f32 weighted
    sum of the +/-1 signs, without ever materializing the dense
    (n_clients, d) fp32 sign matrix. Correct for ARBITRARY per-client
    weights on every backend (0/1 participation masks, data-size
    proportional weights, EF mask * scale). ``backend`` resolves through
    :func:`repro.core.context.resolve_backend`:

      auto    Pallas kernel on TPU, wire.unpack_sum elsewhere (the CPU
              LUT-gather path, bit-identical to the kernel)
      pallas  force the fused kernel (interpret mode off-TPU)
      jnp     force wire.unpack_sum
      dense   legacy dense-matrix path (wire.unpack_sum_dense) — oracle and
              benchmark baseline only

    ``weights_are_mask`` is a STATIC caller guarantee that every weight is
    0 or 1 (a participation mask). The membership contract cannot be checked
    on traced values, so it is plumbed from whoever constructs the mask (the
    round engine via ``RoundContext(weights_are_mask=True)``); when set, the
    jnp backend dispatches to the popcount specialization
    ``wire.unpack_sum_mask`` (bit-identical for any 0/1 mask — integer
    sums). Weighted/EF calls keep the LUT path.

    ``acc`` folds a carried partial sum from previous client shards into
    the result — the streaming cohort driver's reduce-as-you-go hook (see
    wire.unpack_sum for the exactness contract). A flat (8*n_bytes,) f32
    ``acc`` continues the plain left fold; a ``wire.SignFoldAcc`` selects
    the shard-partition-INVARIANT structured fold, which buffers sub-block
    client remainders so the result is bit-identical to one concatenated
    call at ANY shard size — that route always runs through
    ``wire.unpack_sum`` (the pending rows are positional state the kernel
    has no inlet for). The Pallas kernel takes a flat ``acc`` as its
    in-place accumulator: it is aliased onto the kernel's output and the
    client blocks are folded into it in order, the left fold of
    ``wire.unpack_sum``, so the two backends agree to the bit for any
    weights.

    ``debug`` turns on the dynamic membership assertion of the popcount
    path (``wire.check_mask_membership``; debug-wire mode) — it only fires
    on the ``weights_are_mask`` route, where the contract applies.
    """
    backend = resolve_backend("agg", backend)
    if isinstance(acc, wire.SignFoldAcc):
        return unpack_sum(packed, weights, acc)
    if backend == "pallas":
        from repro.kernels.zsign import ops as K
        return K.sign_reduce(packed, weights, acc)
    if backend == "dense":
        return wire.unpack_sum_dense(packed, weights, acc)
    if weights_are_mask:
        return wire.unpack_sum_mask(packed, weights, acc, debug=debug)
    return unpack_sum(packed, weights, acc)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
            for l in jax.tree_util.tree_leaves(tree)))


def _norm_z(z) -> int:
    """Spec-level z values: "inf" (or any z <= 0 / float inf) -> Z_INF."""
    if isinstance(z, str):
        if z.lower() == "inf":
            return znoise.Z_INF
        raise ValueError(f"z must be an int or 'inf', got {z!r}")
    if isinstance(z, float):
        if math.isinf(z):
            return znoise.Z_INF
        if z != int(z):
            raise ValueError(f"z must be an integer or 'inf', got {z!r} — "
                             f"fractional z has no defined noise law here")
        z = int(z)
    return znoise.Z_INF if z <= znoise.Z_INF else z


# ---------------------------------------------------------------------------
# transform stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ErrorFeedback:
    """Per-client error-feedback residual (slot ``"ef"``).

    Pre-codec: the buffer becomes ``p = flat + e``. Post-codec: the new
    residual is ``codec_input - local_decode(payload)`` — exactly what the
    server will NOT see of this client's update. Dead clients keep their
    residual bit-exactly (the engine masks the state update). Composes with
    every codec; with the sign codec the spec parser defaults the codec to
    ``scale="mean_abs"`` so ``ef|zsign`` IS EF-SignSGD.
    """
    spec_name = "ef"
    stateful = True

    def state_spec(self, n_coords: int):
        return (StateSlot("ef", (n_coords,), jnp.float32, "client"),)

    def pre_encode(self, key, p, state, sigma=None, server=None):
        del key, sigma, server
        return p + state["ef"]

    def post_encode(self, state, codec_input, local, server=None):
        del state, server
        return {"ef": codec_input - local}


@dataclasses.dataclass(frozen=True)
class ControlVariate:
    """Compressed SCAFFOLD control variates (SCALLION-style; arXiv:2308.08165).

    Heterogeneous clients drift: client i's local pseudo-gradient estimates
    its OWN data distribution, not the global one — the regime where plain
    sign methods diverge (Stochastic-Sign SGD, arXiv:2002.10940). SCAFFOLD's
    fix is a pair of control variates: a per-client ``c_i`` tracking what
    client i habitually reports, and a shared server variate ``c`` tracking
    the global mean. This stage carries both in the pipeline state substrate
    (slots ``"cv"`` per client, ``"cv_server"`` shared) and keeps the wire
    cost of the downstream codec UNCHANGED — the correction is pre-codec and
    the variate updates are computed from the locally-decoded payload, so
    nothing extra is ever transmitted:

      pre-codec    q_i = p_i - eta * (c_i - c)          (drift correction)
      client       c_i <- c_i + beta * m_i,   m_i = local_decode(payload_i)
      server       c   <- c + beta * (n_live / N) * g_dec        (_finish)

    The server law is EXACT, not approximate: participating clients move
    their variates by beta * m_i, and for every linear-mean codec the
    decoded aggregate is g_dec = (1/n_live) * sum_i m_i, so
    c + (beta * n_live / N) * g_dec == c + (1/N) * sum_i (c_i' - c_i) —
    SCAFFOLD's variate bookkeeping, recovered from the compressed-domain
    accumulator with no dense (n_clients, d) state surface. That exactness
    is WHY this stage refuses nonlinear decode laws (sign ``agg=vote |
    trimmed | median``, top-k ``agg=coord``) at build time: a majority vote
    is not a mean of local decodes, and silently drifting variates are
    worse than a loud error.

    Because the per-client corrections ``c_i - c`` are zero-mean across the
    cohort at the variate fixed point, the server decode law is untouched —
    ``cv|zsign_packed`` ships the same 1 bit/coord payload as
    ``zsign_packed`` and decodes through the same Lemma-1 debias.
    Composes with ``ef`` (the EF residual is ``codec_input - local``, where
    codec_input already carries the cv correction — EF accounts for what
    the codec lost of the CORRECTED buffer) and with ``dp`` upstream.

    ``eta`` scales the correction (SCAFFOLD uses the client step size;
    1.0 applies the raw variate gap), ``beta`` is the variate learning
    rate (1.0 = SCALLION's full replacement-rate tracking).
    """
    eta: float = 1.0
    beta: float = 1.0
    spec_name = "cv"
    stateful = True
    randomized = False
    #: the server-variate update law is exact only for codecs whose
    #: decode_sum is linear in the per-client local decodes — checked at
    #: pipeline build time
    needs_linear_decode = True

    def state_spec(self, n_coords: int):
        return (StateSlot("cv", (n_coords,), jnp.float32, "client"),
                StateSlot("cv_server", (n_coords,), jnp.float32, "server"))

    def pre_encode(self, key, p, state, sigma=None, server=None):
        del key, sigma
        return p - self.eta * (state["cv"] - server["cv_server"])

    def post_encode(self, state, codec_input, local, server=None):
        del codec_input, server
        return {"cv": state["cv"] + self.beta * local}

    def update_server(self, server, g_dec, n_live, n_total):
        """Round-tail server variate update (engine ``_finish``): ``g_dec``
        is the decoded aggregate (possibly pack-padded past n_coords),
        ``n_live`` the traced live weight sum, ``n_total`` the static cohort
        size N."""
        c = server["cv_server"]
        g = g_dec[: c.shape[0]]
        return {"cv_server": c + (self.beta * n_live / n_total) * g}


@dataclasses.dataclass(frozen=True)
class DPTransform:
    """DP clip + Gaussian noise (paper Algorithm 2 client mechanism).

    ``clip`` > 0 clips the flat buffer to that L2 norm; ``noise`` is the
    Gaussian std added afterwards. Instead of ``noise`` you may give a target
    ``eps`` (with ``delta``/``steps``/``q``): the noise multiplier is then
    calibrated through the RDP accountant (core/dp.py) and multiplied by the
    clip norm (the mechanism's sensitivity), so
    ``dp(clip=1.0,eps=2.0,steps=200,q=0.3)`` is a complete client-side DP
    spec.

    When the pipeline's codec is a :class:`SignCodec`, ``Pipeline`` FUSES
    the noise into the codec's sigma at build time: Sign(clip(x) + sigma*xi)
    is sampled straight from its Bernoulli law by the counter-based fused
    encoders, so the dense per-client noise buffer never exists and the wire
    cost stays 1 bit/coord — the paper's "the same noise provides privacy
    and the sign-bias correction", now a structural property of the
    pipeline. Over a dense codec the noise is added here (classic
    DP-FedAvg, 32 bits/coord).
    """
    clip: float = 0.0
    noise: float = 0.0
    eps: float = 0.0
    delta: float = 1e-5
    steps: int = 500
    q: float = 1.0
    #: True iff ``noise`` came from an (eps, delta) calibration — the marker
    #: the Plateau-override refusal keys on (a hand-set noise carries no
    #: privacy promise to protect; the legacy dpgauss law allows overriding
    #: it dynamically)
    calibrated: bool = False
    spec_name = "dp"
    stateful = False

    def __post_init__(self):
        if self.eps > 0.0:
            if self.noise > 0.0:
                raise ValueError("give dp(eps=...) OR dp(noise=...), not "
                                 "both — one target, one mechanism")
            if self.clip <= 0.0:
                raise ValueError("dp(eps=...) needs clip > 0 — the clip norm "
                                 "is the mechanism's sensitivity")
            from repro.core.dp import calibrate_noise
            nm = calibrate_noise(q=self.q, steps=self.steps,
                                 target_eps=self.eps, delta=self.delta,
                                 hi=200.0)
            # eps is consumed into the concrete noise std, so re-running
            # __init__ on this instance (dataclasses.replace) is idempotent
            object.__setattr__(self, "noise", nm * self.clip)
            object.__setattr__(self, "eps", 0.0)
            object.__setattr__(self, "calibrated", True)

    def apply(self, key, flat: jax.Array, sigma=None) -> jax.Array:
        from repro.core.dp import clip_flat
        p = flat
        if self.clip > 0.0:
            p = clip_flat(p, self.clip)
        if (sigma is not None) or self.noise > 0.0:
            sig = self.noise if sigma is None else sigma
            p = p + sig * jax.random.normal(key, p.shape)
        return p

    @property
    def randomized(self) -> bool:
        return self.noise > 0.0


@dataclasses.dataclass(frozen=True)
class SigmaSchedule:
    """Per-layer sigma schedule as a STATIC geometric leaf rescaling.

    One global sigma treats every layer alike, but gradient magnitudes vary
    orders of magnitude across depth — embeddings vs heads. The clean fix
    inside the one-flat-buffer pipeline: scale leaf ``j`` of the ``L``-leaf
    parameter tree by ``m_j = head * (tail / head)^(j / (L - 1))`` BEFORE
    the codec. Because ``Sign(m_j * p + sigma * xi) == Sign(p + (sigma /
    m_j) * xi)``, the wire carries exactly what a per-layer noise scale
    ``sigma / m_j`` would produce — a geometric sigma schedule from the
    first leaf (``sigma / head``) to the last (``sigma / tail``) at zero
    wire cost and zero state. The server decode divides the estimate by the
    same multipliers, restoring each leaf's scale.

    STATELESS and STATIC by design: the multipliers depend only on the tree
    structure (``wire.TreeSpec``), never on data — a data-dependent scale
    could not be inverted server-side without shipping it. The stage
    declares ``needs_tree_spec`` and the engine threads its TreeSpec into
    ``encode(spec=...)`` / ``decode_sum(spec=...)``.

    Composition rules (build-time): must be the FIRST stage (EF residuals
    and dp clipping then live in the scaled domain consistently, round
    over round); refuses ``cv`` outright — the server variate folds the
    UNSCALED decode while client variates would track scaled local decodes,
    so the SCAFFOLD bookkeeping identity breaks.
    """
    head: float = 1.0
    tail: float = 1.0
    spec_name = "sigma_sched"
    stateful = False
    randomized = False
    needs_tree_spec = True

    def __post_init__(self):
        if self.head <= 0.0 or self.tail <= 0.0:
            raise ValueError(f"sigma_sched multipliers must be positive, "
                             f"got head={self.head}, tail={self.tail}")

    def multipliers(self, spec) -> jax.Array:
        """(n_coords,) f32 per-coordinate multiplier, constant per leaf,
        geometric from head (leaf 0) to tail (last leaf)."""
        L = len(spec.shapes)
        if L == 1:
            per_leaf = np.asarray([self.head], np.float32)
        else:
            j = np.arange(L, dtype=np.float64) / (L - 1)
            per_leaf = (self.head * (self.tail / self.head) ** j
                        ).astype(np.float32)
        sizes = np.asarray([int(np.prod(s)) if s else 1
                            for s in spec.shapes])
        return jnp.asarray(np.repeat(per_leaf, sizes))

    def scale(self, p: jax.Array, spec) -> jax.Array:
        m = self.multipliers(spec)
        pad = p.shape[0] - spec.n_coords
        if pad:
            m = jnp.concatenate([m, jnp.ones(pad, p.dtype)])
        return p * m

    def unscale(self, g: jax.Array, spec) -> jax.Array:
        inv = 1.0 / self.multipliers(spec)
        pad = g.shape[0] - spec.n_coords
        if pad:
            inv = jnp.concatenate([inv, jnp.ones(pad, g.dtype)])
        return g * inv


# ---------------------------------------------------------------------------
# wire codec stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseCodec:
    """Uncompressed fp32 wire (identity / FedAvg baseline)."""
    spec_name = "dense"
    randomized = False

    def wire_format(self) -> WireFormat:
        return WireFormat("float32", 32.0, "dense")

    def encode_with_decode(self, key, p, sigma=None, need_decode=False):
        del key, sigma
        return p, (p if need_decode else None)

    def aggregate(self, payload, mask: jax.Array, n_coords: int,
                  acc: jax.Array | None = None) -> jax.Array:
        del n_coords
        return wire.dense_masked_sum(payload, mask, acc)

    def decode_mean(self, flat_mean, sigma=None):
        del sigma
        return flat_mean


@dataclasses.dataclass(frozen=True)
class SignCodec:
    """The unified stochastic-sign wire codec (paper Algorithm 1, line 11).

    Encodes Sign(p + sigma * xi_z) as bitpacked uint8 (8 coords/byte — the
    TRUE 1-bit uplink) and reduces stacked payloads in the compressed domain
    through :func:`sign_reduce`. One codec covers every sign-family member:

      sigma > 0, sigma_mode="fixed"   z-sign (decode debiases by eta_z*sigma;
                                      Lemma 1). sigma == 0.0 is vanilla
                                      SignSGD with the PRNG statically gated
                                      off on every backend.
      sigma_mode="norm"               sto-sign: per-client sigma_i =
                                      ||p_i||_2 (a traced scalar through the
                                      fused threshold), majority-vote decode.
      scale="mean_abs"                EF-SignSGD wire: the payload carries
                                      ONE fp32 magnitude (mean |p|) next to
                                      the bits; aggregation weights become
                                      mask * scale.

    ``sigma`` is an explicit float field — there is no None-able sigma
    anywhere in the stage config; the engine's dynamic (Plateau) sigma
    arrives as a traced override at encode/decode time. ``encode_backend``
    selects the client path ("auto" | "jnp" | "pallas" | "reference"; see
    context.resolve_backend) — the fused counter-based encoders for
    z in {inf, 1}, or the dense jax.random draw ("reference", and always for
    finite z > 1). ``dense_kernel`` routes the dense-reference path through
    the Pallas ``zsign_compress`` kernel (the ``zsign_packed`` spec);
    ``use_kernel`` enables the fused EF+sign Pallas kernel when composed
    under an ``ef`` transform. ``weights_are_mask`` is the static 0/1-mask
    guarantee plumbed from RoundContext (never set on scale-weighted
    aggregation).

    ``agg`` selects the SERVER aggregation law over the +/-1 votes:

      "mean"        the default weighted sign mean (every path above).
      "vote"        coordinate-wise majority vote (Stochastic-Sign SGD /
                    signSGD-with-majority-vote): sign of the signed count,
                    0 at ties. Byzantine-resilient for f < n/2 flippers.
      "trimmed"     coordinate-wise trimmed mean dropping ``trim_f`` votes
                    at each end (``agg=trimmed(f=2)`` sugar sets trim_f).
      "median"      coordinate-wise median (= deepest trim).

    The robust modes aggregate through the integer (signed_count, n_live)
    VOTE PAIR (``wire.vote_accumulator``): still compressed-domain (no
    (n_clients, d) matrix), still one accumulator across streamed shards,
    still one psum across devices — now int32 of size 2*d_pad. They REQUIRE
    the static ``weights_are_mask`` guarantee (fractional weights have no
    vote-count semantics — refused with an error) and ``scale="none"``
    (mean_abs magnitudes are fractional weights by construction). They
    always run the jnp vote path: the Pallas ``sign_reduce`` kernel
    computes f32 weighted sums, not count pairs, so ``agg_backend`` is
    ignored for robust modes. ``debug_wire`` adds the runtime 0/1-mask
    assertion (checkify) on the popcount/vote paths.
    """
    z: int = 1
    sigma: float = 0.0
    sigma_mode: str = "fixed"        # "fixed" | "norm" (sto-sign)
    scale: str = "none"              # "none" | "mean_abs" (EF-SignSGD wire)
    agg_backend: str = "auto"
    encode_backend: str = "auto"
    encode_chunk_tiles: int = 0      # >0: chunked-scan jnp fallback window
    weights_are_mask: bool = False   # static guarantee: weights are 0/1
    dense_kernel: bool = False       # reference path via Pallas zsign_compress
    use_kernel: bool = False         # fused EF+sign Pallas kernel (under ef)
    agg: str = "mean"                # "mean" | "vote" | "trimmed" | "median"
    trim_f: int = 0                  # votes trimmed per end (agg=trimmed)
    debug_wire: bool = False         # runtime 0/1-mask assertion (checkify)
    spec_name = "zsign"
    randomized = True

    def __post_init__(self):
        object.__setattr__(self, "z", _norm_z(self.z))
        if self.sigma_mode not in ("fixed", "norm"):
            raise ValueError(f"sigma_mode must be 'fixed' or 'norm', "
                             f"got {self.sigma_mode!r}")
        if self.scale not in ("none", "mean_abs"):
            raise ValueError(f"scale must be 'none' or 'mean_abs', "
                             f"got {self.scale!r}")
        # "trimmed(f=2)" spec sugar -> agg="trimmed", trim_f=2
        agg = self.agg
        if isinstance(agg, str) and agg.startswith("trimmed("):
            m = re.fullmatch(r"trimmed\(\s*f\s*=\s*(\d+)\s*\)", agg)
            if not m:
                raise ValueError(f"malformed trimmed agg spec {agg!r}; "
                                 f"expected trimmed(f=<int>)")
            f = int(m.group(1))
            if self.trim_f not in (0, f):
                raise ValueError(f"conflicting trim levels: agg={agg!r} vs "
                                 f"trim_f={self.trim_f}")
            object.__setattr__(self, "agg", "trimmed")
            object.__setattr__(self, "trim_f", f)
        if self.agg not in wire.VOTE_AGG_MODES:
            raise ValueError(f"unknown agg mode {self.agg!r}; expected one "
                             f"of {wire.VOTE_AGG_MODES} (trimmed also as "
                             f"'trimmed(f=<int>)')")
        if self.agg == "trimmed" and self.trim_f < 1:
            raise ValueError("agg=trimmed needs trim_f >= 1 — say "
                             "agg=trimmed(f=2) or trim_f=2; trimmed(f=0) is "
                             "exactly agg=mean")
        if self.agg != "trimmed" and self.trim_f != 0:
            raise ValueError(f"trim_f={self.trim_f} only applies to "
                             f"agg=trimmed, not agg={self.agg!r}")
        if self.agg != "mean" and self.scale != "none":
            raise ValueError(
                f"agg={self.agg!r} requires scale='none': scale="
                f"{self.scale!r} aggregation weights clients by fractional "
                f"magnitudes, which have no integer vote-count semantics "
                f"(robust modes count +/-1 votes under a 0/1 mask)")

    def wire_format(self) -> WireFormat:
        layout = "bitpacked+scale" if self.scale == "mean_abs" else "bitpacked"
        return WireFormat("uint8", 1.0, layout)

    # -- client side --------------------------------------------------------

    def _encode_dense(self, key, flat, sig, add_noise):
        """Dense-draw statistical oracle (and the finite z > 1 path)."""
        if self.dense_kernel:
            from repro.kernels.zsign import ops as K
            if not add_noise:
                # vanilla-SignSGD mode: no noise is drawn (flat doubles as a
                # dummy operand; sigma == 0 makes it a no-op in the kernel)
                return K.zsign_compress(flat, flat, 0.0)
            return K.zsign_compress(
                flat, znoise.sample_z_noise(key, flat.shape, self.z), sig)
        if add_noise:
            flat = flat + sig * znoise.sample_z_noise(key, flat.shape, self.z)
        return pack_flat(flat)

    def _encode_bits(self, key, flat, sig, add_noise):
        backend = resolve_backend("encode", self.encode_backend)
        if backend == "reference" or (add_noise
                                      and not znoise.counter_supported(self.z)):
            return self._encode_dense(key, flat, sig, add_noise)
        if backend == "pallas":
            from repro.kernels.zsign import ops as K
            return K.zsign_encode_fused(flat, key, sig, z=self.z,
                                        add_noise=add_noise)
        return fused_sign_encode_jnp(flat, key, sig, z=self.z,
                                     add_noise=add_noise,
                                     chunk_tiles=self.encode_chunk_tiles)

    def _noise_gate(self, sigma):
        """The ONE place the noise gate is decided: a static sigma of 0.0
        (vanilla SignSGD) disables the draw on every backend; a dynamic
        sigma (possibly traced) always flows through — a runtime 0 degrades
        exactly inside stochastic_sign_bits."""
        if self.sigma_mode == "norm":
            return None, True     # sigma computed from the buffer at encode
        add_noise = (sigma is not None) or self.sigma > 0.0
        return (self.sigma if sigma is None else sigma), add_noise

    def encode_with_decode(self, key, p, sigma=None, need_decode=False):
        """-> (payload, local_decode or None). ``local_decode`` is the exact
        per-client value the server attributes to this payload — what an
        ``ef`` transform upstream subtracts to form its residual."""
        d = p.shape[0]
        sig, add_noise = self._noise_gate(sigma)
        if sig is None:
            sig = jnp.linalg.norm(p)
        if self.scale == "mean_abs":
            s = jnp.mean(jnp.abs(p))
            if not add_noise:
                # EF-SignSGD proper: noise-free signs; residual uses the same
                # p >= 0 convention as the wire payload, so EF accounts
                # exactly for what the server decodes (jnp.sign's 0-at-0
                # would leak +scale per round on zero coords)
                packed = pack_flat(p)
                dec = (s * jnp.where(p >= 0, 1.0, -1.0)
                       if need_decode else None)
            else:
                packed = self._encode_bits(key, p, sig, add_noise)
                dec = (s * unpack_signs(packed)[:d].astype(jnp.float32)
                       if need_decode else None)
            return {"packed": packed, "scale": s}, dec
        packed = self._encode_bits(key, p, sig, add_noise)
        if not need_decode:
            return packed, None
        if self.sigma_mode == "norm" or not add_noise:
            factor = 1.0
        else:
            factor = znoise.eta_z(self.z) * sig
        return packed, factor * unpack_signs(packed)[:d].astype(jnp.float32)

    # -- server side --------------------------------------------------------

    def aggregate(self, payload, mask: jax.Array, n_coords: int,
                  acc: jax.Array | None = None) -> jax.Array:
        del n_coords
        if self.scale == "mean_abs":
            # weights = mask * per-client scale: the fused reduce handles the
            # scale-weighted sum directly in the compressed domain.
            return sign_reduce(payload["packed"], mask * payload["scale"],
                               self.agg_backend, acc=acc)
        if self.agg != "mean":
            if not self.weights_are_mask:
                raise ValueError(
                    f"agg={self.agg!r} requires the static weights_are_mask "
                    f"guarantee (0/1 participation masks): robust sign "
                    f"aggregation counts +/-1 votes, and fractional weights "
                    f"(importance/arrival sampler tiers, data-size weights) "
                    f"have no vote-count semantics. Run under "
                    f"RoundContext(weights_are_mask=True) with a uniform "
                    f"0/1 sampler, or use agg=mean.")
            return wire.vote_accumulator(payload, mask, acc,
                                         debug=self.debug_wire)
        return sign_reduce(payload, mask, self.agg_backend,
                           weights_are_mask=self.weights_are_mask, acc=acc,
                           debug=self.debug_wire)

    def fold_init(self, enc_shape):
        """Structured streaming-fold accumulator, or None when the flat
        zero accumulator is already partition-exact.

        The fp32-WEIGHTED aggregation routes (``scale="mean_abs"`` EF
        wires, and plain mean without the static 0/1-mask guarantee) are
        order-sensitive: a flat fold closes an 8-client LUT block at every
        shard boundary, so off-block shard sizes re-associate the fp32
        sums. For those routes this returns a ``wire.SignFoldAcc`` sized
        from the payload's wire width — the pending-row carry that makes
        the shard fold bit-identical to one concatenated reduce at ANY
        shard partition. Mask-guaranteed and vote routes are integer-exact
        under any association already and keep the flat accumulator
        (None). ``enc_shape`` is the eval_shape of one shard's encoded
        payload stack (dict for the bitpacked+scale wire)."""
        weighted = (self.scale == "mean_abs"
                    or (self.agg == "mean" and not self.weights_are_mask))
        if not weighted:
            return None
        packed = enc_shape["packed"] if isinstance(enc_shape, dict) \
            else enc_shape
        return wire.sign_fold_init(int(packed.shape[-1]))

    def decode_mean(self, flat_mean, sigma=None):
        if self.scale == "mean_abs" or self.sigma_mode == "norm":
            # magnitudes already in the aggregation weights / majority vote
            del sigma
            return flat_mean
        if sigma is None:
            scale = (znoise.eta_z(self.z) * self.sigma
                     if self.sigma > 0.0 else 1.0)
        else:
            scale = znoise.eta_z(self.z) * sigma
        return flat_mean * scale

    def decode_sum(self, enc_sum, n_live, sigma=None):
        """Server estimate from the aggregate output + live count.

        The one server-side decode entry point: for ``agg="mean"`` it is
        ``decode_mean(enc_sum / n_live)`` exactly; for the robust modes
        ``enc_sum`` is the int32 vote pair and the estimate comes from the
        closed forms in ``wire.vote_decode``. Decode laws per mode:

          mean / trimmed   debiased by eta_z * sigma (Lemma 1 — the trimmed
                           mean of the +/-1 votes estimates the same
                           clipped expectation as the mean, so the same
                           linear debias applies; exact only without
                           adversaries, which is the point of trimming).
          vote / median    returned RAW in {-1, 0, +1}: a majority decision
                           is scale-invariant, so there is nothing to
                           debias — the server takes signSGD-style
                           fixed-magnitude steps of server_lr per coord.
        """
        if self.agg == "mean":
            return self.decode_mean(enc_sum / n_live, sigma=sigma)
        est = wire.vote_decode(enc_sum, self.agg, self.trim_f)
        if self.agg == "trimmed":
            return self.decode_mean(est, sigma=sigma)
        return est


@dataclasses.dataclass(frozen=True)
class QSGDCodec:
    """Unbiased stochastic quantizer of Alistarh et al. (paper Definition 2);
    with FedAvg local steps this is FedPAQ/FedCOM. ``s`` quantization levels;
    wire cost derives from s: ceil(log2(2s+1)) bits/coord (+ one fp32 norm,
    amortized)."""
    s: int = 1
    spec_name = "qsgd"
    randomized = True

    def wire_format(self) -> WireFormat:
        return WireFormat("float32",
                          float(math.ceil(math.log2(2 * self.s + 1))),
                          "dense")

    def encode_with_decode(self, key, p, sigma=None, need_decode=False):
        del sigma
        nrm = jnp.linalg.norm(p) + 1e-12
        r = jnp.abs(p) / nrm * self.s
        low = jnp.floor(r)
        up = jax.random.bernoulli(key, jnp.clip(r - low, 0.0, 1.0), p.shape)
        lvl = (low + up.astype(jnp.float32)) / self.s
        q = nrm * jnp.sign(p) * lvl
        return q, (q if need_decode else None)

    def aggregate(self, payload, mask: jax.Array, n_coords: int,
                  acc: jax.Array | None = None) -> jax.Array:
        del n_coords
        return wire.dense_masked_sum(payload, mask, acc)

    def decode_mean(self, flat_mean, sigma=None):
        del sigma
        return flat_mean


@dataclasses.dataclass(frozen=True)
class TopKCodec:
    """Global top-k sparsifier: keep the top ``frac`` of the flat buffer by
    magnitude (GLOBAL across all tensors). COO wire format (values, indices),
    64*frac bits/coord. STATELESS — compose ``ef|topk`` for the classic
    error-corrected variant (the legacy ``topk`` compressor is exactly that
    pipeline).

    Selection runs as a two-stage chunked top-k when d exceeds ``chunk``:
    per-chunk ``lax.top_k`` candidates, then a final top-k over the
    candidate pool — O(d log k / chunk)-ish work instead of one full-buffer
    sort-like pass over all d coordinates, and exactly equivalent to the
    single-stage selection (every global top-k element is in its own chunk's
    top-k; tie-breaking by lowest index is preserved because candidates are
    ordered by (chunk, rank) — verified exhaustively in tests).

    ``chunk=0`` (the default) AUTO-TUNES the chunk size from the buffer at
    trace time: the first stage touches d coordinates and the second
    touches the candidate pool of (d / chunk) * k, so the pool matches the
    chunk at chunk ~ sqrt(d * k) — ``_resolve_chunk`` rounds that up to a
    power of two and clamps it to [4096, 2^20] (below 4096 the per-chunk
    launch overhead dominates; above 2^20 the first stage stops fitting in
    cache). A positive ``chunk`` pins the size explicitly; the selected
    set is identical either way.

    ``agg="coord"`` is the FedDropoutAvg-style COORDINATE-PARTICIPATION
    normalization: because each client reports a different index set, the
    global-n_live mean ("mean") shrinks every coordinate by (reporters /
    n_live). "coord" instead scatter-adds a per-coordinate reporter COUNT
    next to the value sum (a (2, n_coords) accumulator — still additive
    across shards, still one psum across devices) and the decode divides
    each coordinate by ITS OWN reporter count, so a coordinate reported by
    3 of 50 live clients gets the mean of those 3 values, not 3/50 of it.
    Unreported coordinates decode to 0. Composes with ``ef`` (the residual
    is client-local, against the client's OWN scatter — unchanged), but the
    server estimate is no longer linear in the payload stack, so the
    EF-top-k contraction bound applies to the "mean" law only.
    """
    frac: float = 0.01
    chunk: int = 0      # 0 = auto-tune from (d, k); >0 pins the chunk size
    agg: str = "mean"   # "mean" | "coord" (per-coordinate participation)
    spec_name = "topk"
    randomized = False

    def __post_init__(self):
        if self.agg not in ("mean", "coord"):
            raise ValueError(f"topk agg must be 'mean' or 'coord', "
                             f"got {self.agg!r}")
        if self.chunk < 0:
            raise ValueError(f"topk chunk must be 0 (auto) or positive, "
                             f"got {self.chunk}")

    def wire_format(self) -> WireFormat:
        # fp32 value + int32 index per kept coordinate.
        return WireFormat("float32", 64.0 * self.frac, "sparse_coo")

    @staticmethod
    def _resolve_chunk(d: int, k: int) -> int:
        """Auto-tuned chunk size: balance the two stages (first touches d,
        second touches the (d / chunk) * k candidate pool) at
        chunk ~ sqrt(d * k), rounded up to a power of two and clamped to
        [4096, 2^20]. Static per (d, k) — no retrace churn."""
        c = max(1, int(math.sqrt(d * max(1, k))))
        return min(1 << 20, max(4096, 1 << (c - 1).bit_length()))

    def _select(self, score: jax.Array, k: int) -> jax.Array:
        """Indices of the k largest scores (ties -> lowest index first)."""
        d = score.shape[0]
        chunk = self.chunk or self._resolve_chunk(d, k)
        if d <= chunk or k >= chunk:
            _, idx = jax.lax.top_k(score, k)
            return idx
        n_chunks = -(-d // chunk)
        pad = n_chunks * chunk - d
        s = jnp.pad(score, (0, pad), constant_values=-jnp.inf)
        cand_val, cand_idx = jax.lax.top_k(s.reshape(n_chunks, chunk), k)
        base = (jnp.arange(n_chunks, dtype=cand_idx.dtype)[:, None]
                * chunk)
        cand_idx = (cand_idx + base).reshape(-1)
        _, sel = jax.lax.top_k(cand_val.reshape(-1), k)
        return cand_idx[sel]

    def encode_with_decode(self, key, p, sigma=None, need_decode=False):
        del key, sigma
        k = max(1, int(p.shape[0] * self.frac))
        idx = self._select(jnp.abs(p), k)
        vals = p[idx]
        payload = {"values": vals, "indices": idx}
        if not need_decode:
            return payload, None
        # local decode scatters the kept values back; the EF residual
        # p - decode is then exactly p with the selected coords zeroed
        return payload, jnp.zeros_like(p).at[idx].set(vals)

    def aggregate(self, payload, mask: jax.Array, n_coords: int,
                  acc: jax.Array | None = None) -> jax.Array:
        if self.agg == "coord":
            vals = wire.scatter_sum_coo(
                payload["values"], payload["indices"], mask, n_coords,
                None if acc is None else acc[0])
            cnt = wire.scatter_sum_coo(
                jnp.ones_like(payload["values"]), payload["indices"], mask,
                n_coords, None if acc is None else acc[1])
            return jnp.stack([vals, cnt])
        return wire.scatter_sum_coo(payload["values"], payload["indices"],
                                    mask, n_coords, acc)

    def decode_mean(self, flat_mean, sigma=None):
        del sigma
        return flat_mean

    def decode_sum(self, enc_sum, n_live, sigma=None):
        del sigma
        if self.agg == "coord":
            # per-coordinate mean over the clients that REPORTED it; the
            # value row is exactly 0 wherever the count row is 0
            return enc_sum[0] / jnp.maximum(enc_sum[1], 1.0)
        return enc_sum / n_live


# ---------------------------------------------------------------------------
# the pipeline combinator
# ---------------------------------------------------------------------------

_TRANSFORM_SPECS = {"ef": ErrorFeedback, "dp": DPTransform,
                    "cv": ControlVariate, "sigma_sched": SigmaSchedule}


def _sign_spec(**defaults):
    def build(**kw):
        merged = dict(defaults)
        merged.update(kw)
        return SignCodec(**merged)
    return build


_CODEC_SPECS = {
    "zsign": _sign_spec(),
    "zsign_packed": _sign_spec(encode_backend="pallas", dense_kernel=True),
    "stosign": _sign_spec(z=znoise.Z_INF, sigma_mode="norm"),
    "qsgd": QSGDCodec,
    "topk": TopKCodec,
    "dense": DenseCodec,
    "identity": DenseCodec,
}


def _parse_value(v: str):
    low = v.lower()
    if low in ("true", "false"):
        return low == "true"
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return v


def _split_args(args: str, tok: str):
    """Split a stage's argument list on TOP-LEVEL commas only, so nested
    call-style values (``agg=trimmed(f=2)``) stay one argument."""
    parts, cur, depth = [], [], 0
    for ch in args:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth < 0:
                raise ValueError(f"unbalanced parentheses in {tok!r}")
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if depth != 0:
        raise ValueError(f"unbalanced parentheses in {tok!r}")
    parts.append("".join(cur))
    return parts


def _parse_stage(tok: str) -> Tuple[str, dict]:
    tok = tok.strip()
    if "(" in tok:
        if not tok.endswith(")"):
            raise ValueError(f"malformed stage spec {tok!r}")
        name, args = tok[:-1].split("(", 1)
        kw = {}
        for part in filter(None,
                           (p.strip() for p in _split_args(args, tok))):
            if "=" not in part:
                raise ValueError(f"stage argument {part!r} in {tok!r} must "
                                 f"be key=value")
            k, v = part.split("=", 1)
            kw[k.strip()] = _parse_value(v.strip())
        return name.strip(), kw
    return tok, {}


def parse_spec(spec: str):
    """Spec string -> (transforms tuple, codec). Grammar:

        spec  := stage ("|" stage)*
        stage := name | name "(" k "=" v ("," k "=" v)* ")"

    Every stage but the last must be a transform (``ef``, ``dp``, ``cv``,
    ``sigma_sched``);
    the last must be a codec (``zsign``, ``zsign_packed``, ``stosign``,
    ``qsgd``, ``topk``, ``dense``/``identity``). Values parse as int, float,
    bool or
    bare string (e.g. ``scale=mean_abs``, ``z=inf``). Convenience defaults:
    an ``ef`` transform in front of a sign codec sets ``scale="mean_abs"``
    unless given explicitly — ``"ef|zsign"`` IS EF-SignSGD.
    """
    toks = [t for t in (p.strip() for p in spec.split("|")) if t]
    if not toks:
        raise ValueError("empty pipeline spec")
    transforms = []
    for tok in toks[:-1]:
        name, kw = _parse_stage(tok)
        if name not in _TRANSFORM_SPECS:
            raise ValueError(
                f"unknown transform stage {name!r} in {spec!r}; transforms: "
                f"{sorted(_TRANSFORM_SPECS)} (codecs must come last)")
        transforms.append(_TRANSFORM_SPECS[name](**kw))
    name, kw = _parse_stage(toks[-1])
    if name not in _CODEC_SPECS:
        raise ValueError(f"unknown codec stage {name!r} in {spec!r}; codecs: "
                         f"{sorted(_CODEC_SPECS)}")
    explicit_scale = "scale" in kw
    codec = _CODEC_SPECS[name](**kw)
    # convenience default: ef over the NOISE-FREE fixed-sigma sign codec is
    # EF-SignSGD, whose wire carries the mean-abs magnitude. Noisy z-sign
    # (sigma > 0, debiased by eta_z * sigma) and sto-sign (norm mode,
    # majority vote) keep their own decode laws under ef. Robust agg modes
    # opt out too: they require scale='none' (mean_abs magnitudes are
    # fractional weights), so "ef|zsign(agg=vote)" is EF over the raw-sign
    # wire with majority-vote decode.
    if (isinstance(codec, SignCodec) and not explicit_scale
            and codec.sigma == 0.0 and codec.sigma_mode == "fixed"
            and codec.agg == "mean"
            and any(isinstance(t, ErrorFeedback) for t in transforms)):
        codec = dataclasses.replace(codec, scale="mean_abs")
    return tuple(transforms), codec


@dataclasses.dataclass(frozen=True)
class Pipeline:
    """Transforms + one wire codec; the engine-facing compressor.

    Build from a spec string (``Pipeline("ef|zsign")``) or from stage
    instances (``Pipeline((ErrorFeedback(),), TopKCodec(frac=0.01))``).
    Frozen and hashable: deployments rebind backend policy with
    :meth:`with_context`, which returns a new pipeline.

    Construction-time rules (idempotent, applied in ``__post_init__``):

      * stateful stages declare named slots (``state_spec``); slot names
        must be unique across stages — a collision is a build-time error.
        The pipeline state the engine replicates per client is the keyed
        dict ``{slot_name: buffer}`` over the client-scope slots;
      * at most one ``ef`` transform (two residuals would double-count the
        compression error);
      * a ``dp`` transform's noise is FUSED into a downstream
        :class:`SignCodec`'s sigma (see :class:`DPTransform`): the codec
        must not carry its own sigma at the same time.
    """
    transforms: Any = ()
    codec: Any = None
    name: str = ""

    def __post_init__(self):
        transforms, codec, name = self.transforms, self.codec, self.name
        if isinstance(transforms, str):
            spec = transforms
            if codec is not None:
                raise ValueError("give either a spec string or stages, "
                                 "not both")
            transforms, codec = parse_spec(spec)
            name = name or spec
        transforms = tuple(transforms)
        if codec is None:
            raise ValueError("pipeline needs a wire codec as its last stage")
        ef_idx = [i for i, t in enumerate(transforms)
                  if isinstance(t, ErrorFeedback)]
        if len(ef_idx) > 1:
            raise ValueError("at most one ef transform per pipeline")
        # dp-noise fusion into the sign codec (see DPTransform docstring)
        if isinstance(codec, SignCodec):
            fused = []
            for t in transforms:
                if isinstance(t, DPTransform) and t.noise > 0.0:
                    if codec.z != 1 or codec.sigma_mode != "fixed":
                        # the dp accountant assumes the GAUSSIAN mechanism;
                        # a z != 1 sign codec samples a different noise law
                        # (z=inf is bounded uniform), which would silently
                        # void the calibrated (eps, delta) guarantee
                        raise ValueError(
                            "dp noise is Gaussian: the sign codec must be "
                            "z=1 with sigma_mode='fixed' to carry it "
                            f"(got z={codec.z}, sigma_mode="
                            f"{codec.sigma_mode!r})")
                    if codec.sigma > 0.0:
                        raise ValueError(
                            "ambiguous noise: both the dp stage and the sign "
                            "codec carry a sigma — set it on one stage only")
                    codec = dataclasses.replace(codec, sigma=t.noise)
                    t = dataclasses.replace(t, noise=0.0, eps=0.0)
                fused.append(t)
            transforms = tuple(fused)
        object.__setattr__(self, "transforms", transforms)
        object.__setattr__(self, "codec", codec)
        object.__setattr__(self, "name", name or self.spec)
        randomized = [i for i, t in enumerate(transforms)
                      if getattr(t, "randomized", False)]
        if getattr(codec, "randomized", False):
            randomized.append(len(transforms))
        object.__setattr__(self, "_n_random", len(randomized))
        stateful = tuple(i for i, t in enumerate(transforms)
                         if getattr(t, "stateful", False))
        object.__setattr__(self, "_stateful_idx", stateful)
        # sigma_sched: at most one, FIRST in the pipeline (so every later
        # stage — EF residuals, dp clip — lives consistently in the scaled
        # domain), never with cv (the server variate folds the unscaled
        # decode — domain mismatch)
        scheds = [i for i, t in enumerate(transforms)
                  if isinstance(t, SigmaSchedule)]
        if len(scheds) > 1:
            raise ValueError("at most one sigma_sched stage per pipeline")
        if scheds:
            if any(isinstance(t, ControlVariate) for t in transforms):
                raise ValueError(
                    "sigma_sched cannot compose with cv: the server "
                    "variate update folds the UNSCALED decoded aggregate "
                    "while client variates would track scaled local "
                    "decodes — the SCAFFOLD bookkeeping identity breaks")
            if scheds[0] != 0:
                raise ValueError(
                    "sigma_sched must be the first stage (e.g. "
                    "'sigma_sched(...)|ef|zsign'): it rescales the raw "
                    "pseudo-gradient, so residuals and clipping must "
                    "happen in the scaled domain")
        object.__setattr__(self, "_needs_spec", any(
            getattr(t, "needs_tree_spec", False) for t in transforms))
        # slot-name collision check (shapes irrelevant at build time) —
        # multi-state pipelines fail loudly here, not deep in the engine
        slots0 = cstate_lib.collect_slots(
            [transforms[i] for i in stateful], 0)
        object.__setattr__(self, "_has_server_state",
                           any(s.scope == "server" for s in slots0))
        # control variates need a decode law linear in the per-client local
        # decodes: the server variate update c+ = c + beta*(n_live/N)*g_dec
        # is exact only when g_dec is the mean of what clients attributed
        # locally. Vote/count laws are not — refuse at build, not at drift.
        linear_needers = [t for t in transforms
                          if getattr(t, "needs_linear_decode", False)]
        if linear_needers:
            bad = None
            if isinstance(codec, SignCodec) and codec.agg != "mean":
                bad = f"the sign codec's agg={codec.agg!r} vote law"
            elif isinstance(codec, TopKCodec) and codec.agg != "mean":
                bad = "topk's agg='coord' per-coordinate count law"
            if bad is not None:
                raise ValueError(
                    f"{linear_needers[0].spec_name} control variates "
                    f"require a server decode LINEAR in the per-client "
                    f"local decodes (the variate update is exact only for "
                    f"mean-law codecs), but {bad} decodes through a "
                    f"nonlinear count — use agg=mean or drop the cv stage")
        # dynamic (Plateau) sigma routes to the sign codec when present,
        # else to the last noise-bearing dp transform (legacy dpgauss law).
        # The noise-free EF-SignSGD wire (scale=mean_abs, sigma == 0) has NO
        # consumer: the legacy EFSignCompressor ignored the engine's dynamic
        # sigma, and silently noising EF payloads under --plateau would be a
        # training-dynamics change (want noisy EF? say zsign(sigma=...)).
        if isinstance(codec, SignCodec):
            consumer = (None if codec.scale == "mean_abs"
                        and codec.sigma == 0.0 else "codec")
        else:
            dps = [i for i, t in enumerate(transforms)
                   if isinstance(t, DPTransform) and t.noise > 0.0]
            consumer = dps[-1] if dps else "codec"
        object.__setattr__(self, "_sigma_stage", consumer)

    # -- construction helpers ------------------------------------------------

    @property
    def spec(self) -> str:
        """Canonical spec string (non-default stage fields spelled out)."""
        def stage_str(s):
            kw = []
            if dataclasses.is_dataclass(s):
                for f in dataclasses.fields(s):
                    v = getattr(s, f.name)
                    if v != f.default:
                        kw.append(f"{f.name}={v}")
            return s.spec_name + (f"({','.join(kw)})" if kw else "")
        return "|".join([stage_str(t) for t in self.transforms]
                        + [stage_str(self.codec)])

    def with_context(self, ctx: RoundContext) -> "Pipeline":
        """Rebind the deployment's backend policy onto every sign stage.

        ``None`` backends in the context keep the stage's own setting (e.g.
        ``zsign_packed`` stays pinned to pallas); explicit values override.
        ``weights_are_mask`` is only applied to pure-mask aggregation —
        scale-weighted (EF) reduces keep the general LUT path.
        ``dynamic_sigma`` is refused on pipelines whose ``dp`` stage was
        (eps, delta)-CALIBRATED: the Plateau controller overriding that
        noise would silently void the guarantee. A hand-set ``dp(noise=..)``
        carries no such promise and keeps the legacy dpgauss law (the
        dynamic sigma overrides it).
        """
        if ctx.dynamic_sigma and any(
                isinstance(t, DPTransform) and t.calibrated
                for t in self.transforms):
            raise ValueError(
                "dynamic (Plateau) sigma cannot run over an eps-calibrated "
                "dp stage: the loss-adaptive override would replace the "
                "privacy-calibrated noise and void the (eps, delta) "
                "guarantee")
        codec = self.codec
        if isinstance(codec, SignCodec):
            kw = {}
            if ctx.agg_backend is not None:
                kw["agg_backend"] = ctx.agg_backend
            if ctx.encode_backend is not None:
                kw["encode_backend"] = ctx.encode_backend
            if ctx.weights_are_mask and codec.scale == "none":
                kw["weights_are_mask"] = True
            if ctx.debug_wire and not codec.debug_wire:
                kw["debug_wire"] = True
            if kw:
                codec = dataclasses.replace(codec, **kw)
        if codec is self.codec:
            return self
        return dataclasses.replace(self, codec=codec)

    def __getattr__(self, item):
        # legacy-compat delegation: codec hyper-parameters (z, sigma, frac,
        # s, _select, ...) read through the pipeline, as they did when each
        # combination was its own class. Dunder lookups never delegate.
        if item.startswith("__"):
            raise AttributeError(item)
        codec = self.__dict__.get("codec")
        if codec is None:
            raise AttributeError(item)
        try:
            return getattr(codec, item)
        except AttributeError:
            raise AttributeError(
                f"{type(self).__name__!s} object has no attribute {item!r}")

    # -- engine-facing compressor interface ---------------------------------

    @property
    def wire_bits_per_coord(self) -> float:
        return self.wire_format().bits_per_coord

    def wire_format(self) -> WireFormat:
        return self.codec.wire_format()

    @property
    def needs_tree_spec(self) -> bool:
        """True when a stage (sigma_sched) needs the engine's wire.TreeSpec
        threaded into ``encode(spec=...)`` / ``decode_sum(spec=...)`` —
        the engine gates the kwarg on this capability flag."""
        return self._needs_spec

    def state_slots(self, n_coords: int):
        """All :class:`StateSlot` declarations of this pipeline's stateful
        stages, in stage order (both client- and server-scope)."""
        return cstate_lib.collect_slots(
            [self.transforms[i] for i in self._stateful_idx], n_coords)

    def init_state(self, n_coords: int):
        """Zero-initialized per-client state: the keyed ``{slot: buffer}``
        dict over client-scope slots, or None for stateless pipelines."""
        return cstate_lib.init_tree(self.state_slots(n_coords), "client")

    def init_server_state(self, n_coords: int):
        """Zero-initialized SHARED server-scope state (control variates):
        the keyed ``{slot: buffer}`` dict over server-scope slots, or None.
        One tree per deployment — the engine replicates it across devices
        and threads it into every client encode (``encode(server=...)``)."""
        return cstate_lib.init_tree(self.state_slots(n_coords), "server")

    def update_server(self, server, g_dec, n_live, n_total):
        """Round-tail update of the shared server-scope state from the
        DECODED aggregate — called once per round by the engine's finish
        step, after ``decode_sum``. Each stateful stage with an
        ``update_server`` hook contributes its slots; stages without one
        keep theirs unchanged. No per-client payloads are consumed here:
        server slots update from the O(d) compressed-domain fold output
        only, so no dense (n_clients, d) surface ever exists."""
        if server is None:
            return None
        new = dict(server)
        for i in self._stateful_idx:
            hook = getattr(self.transforms[i], "update_server", None)
            if hook is not None:
                new.update(hook(server, g_dec, n_live, n_total))
        return new

    def _stage_key(self, key, i: int):
        # a single random stage consumes the raw client key (bit-compat with
        # the legacy monolithic compressors); multiple random stages get
        # fold_in-derived subkeys
        if self._n_random <= 1 or key is None:
            return key
        return jax.random.fold_in(key, i)

    def _ef_kernel_path(self, sigma) -> bool:
        return (len(self.transforms) == 1
                and isinstance(self.transforms[0], ErrorFeedback)
                and isinstance(self.codec, SignCodec)
                and self.codec.use_kernel
                and self.codec.scale == "mean_abs"
                and self.codec.sigma_mode == "fixed"
                and self.codec.sigma == 0.0
                and (sigma is None or self._sigma_stage is None))

    def encode(self, key, flat: jax.Array, state, sigma=None, server=None,
               spec=None):
        """(payload, new_state). ``sigma`` is the engine's dynamic (Plateau)
        override, routed to the pipeline's one sigma consumer. ``server`` is
        the shared server-scope state tree (``init_server_state``) — REQUIRED
        when a stage declares server slots (control variates), unused
        otherwise; the engine passes ``ServerState.comp_server``. ``spec``
        is the flat buffer's wire.TreeSpec — REQUIRED when
        ``needs_tree_spec`` (sigma_sched), unused otherwise."""
        if self._has_server_state and server is None:
            raise ValueError(
                "pipeline declares server-scope state slots (control "
                "variates): encode needs the shared server tree — pass "
                "server=init_server_state(n_coords) (the engine threads "
                "ServerState.comp_server here)")
        if self._needs_spec and spec is None:
            raise ValueError(
                "pipeline declares a tree-structured stage (sigma_sched): "
                "encode needs the flat buffer's wire.TreeSpec — pass "
                "spec=wire.tree_spec(params) (the engine threads its "
                "round TreeSpec here)")
        if self._ef_kernel_path(sigma):
            # one fused VMEM pass: bitpacked payload + residual together
            from repro.kernels.efsign import ops as EK
            res = state["ef"]
            scale = jnp.mean(jnp.abs(flat + res))
            packed, res = EK.ef_sign_encode(flat, res, scale)
            return {"packed": packed, "scale": scale}, {"ef": res}
        p = flat
        for i, t in enumerate(self.transforms):
            sig_i = sigma if self._sigma_stage == i else None
            if getattr(t, "needs_tree_spec", False):
                p = t.scale(p, spec)
            elif getattr(t, "stateful", False):
                p = t.pre_encode(self._stage_key(key, i), p, state,
                                 sigma=sig_i, server=server)
            else:
                p = t.apply(self._stage_key(key, i), p, sigma=sig_i)
        payload, local = self.codec.encode_with_decode(
            self._stage_key(key, len(self.transforms)), p,
            sigma=(sigma if self._sigma_stage == "codec" else None),
            need_decode=bool(self._stateful_idx))
        if not self._stateful_idx:
            return payload, state
        new_state = dict(state)
        for i in self._stateful_idx:
            new_state.update(self.transforms[i].post_encode(state, p, local,
                                                            server=server))
        return payload, new_state

    def aggregate(self, payload, mask: jax.Array, n_coords: int,
                  acc: jax.Array | None = None) -> jax.Array:
        """Masked SUM over the leading client axis of stacked payloads.
        ``n_coords`` is the true (unpadded) coordinate count from the
        engine's TreeSpec — sparse layouts need it to materialize the dense
        sum; others may ignore it and return padded buffers. ``acc`` folds a
        carried partial sum from previous client shards into the result —
        the streaming cohort driver aggregates shard-by-shard through this
        one hook, so the full-cohort payload stack never exists (sign
        families carry O(d/8) of state per fold; dense codecs carry one
        (d,) f32 buffer)."""
        return self.codec.aggregate(payload, mask, n_coords, acc)

    def fold_init(self, enc_shape):
        """Streaming-fold accumulator INITIALIZER for the round driver.

        Returns the codec's structured carry when shard-partition-exact
        folding needs one (SignCodec's fp32-weighted routes return a
        ``wire.SignFoldAcc``), or None when a flat zero accumulator shaped
        by ``aggregate``'s own output is already exact — the driver falls
        back to its eval_shape zeros there. ``enc_shape`` is the
        ``jax.eval_shape`` of one shard's encoded payload stack."""
        init = getattr(self.codec, "fold_init", None)
        return None if init is None else init(enc_shape)

    def fold_finalize(self, acc):
        """Close a streaming-fold accumulator into the plain ``aggregate``
        output the decode path consumes. Structured carries flush their
        pending state (``wire.sign_fold_finalize``); flat accumulators pass
        through unchanged. Multi-device rounds MUST finalize per device
        BEFORE the cross-device psum — pending rows are positional, not
        additive."""
        if isinstance(acc, wire.SignFoldAcc):
            return wire.sign_fold_finalize(acc)
        return acc

    def _unscale(self, g: jax.Array, spec) -> jax.Array:
        # invert tree-structured stages (sigma_sched) in reverse stage order
        if not self._needs_spec:
            return g
        if spec is None:
            raise ValueError(
                "pipeline declares a tree-structured stage (sigma_sched): "
                "decode needs the round's wire.TreeSpec — pass spec=")
        for t in reversed(self.transforms):
            if getattr(t, "needs_tree_spec", False):
                g = t.unscale(g, spec)
        return g

    def decode_mean(self, flat_mean: jax.Array, sigma=None,
                    spec=None) -> jax.Array:
        return self._unscale(self.codec.decode_mean(
            flat_mean,
            sigma=(sigma if self._sigma_stage == "codec" else None)), spec)

    def decode_sum(self, enc_sum: jax.Array, n_live: jax.Array,
                   sigma=None, spec=None) -> jax.Array:
        """Server estimate from the ``aggregate`` output + live count — the
        engine's decode entry point. For codecs whose aggregate is the plain
        masked sum this is ``decode_mean(enc_sum / n_live)`` exactly; codecs
        with a non-mean law (SignCodec robust ``agg=`` modes, TopKCodec
        ``agg=coord``) own the full sum -> estimate mapping through their
        ``decode_sum``. ``spec`` (the round's TreeSpec) is required exactly
        when ``needs_tree_spec`` — sigma_sched inverts its leaf scaling
        here."""
        sig = sigma if self._sigma_stage == "codec" else None
        dec = getattr(self.codec, "decode_sum", None)
        if dec is not None:
            return self._unscale(dec(enc_sum, n_live, sigma=sig), spec)
        return self._unscale(self.codec.decode_mean(enc_sum / n_live,
                                                    sigma=sig), spec)

    def reduce_across_devices(self, acc: jax.Array,
                              axis_name: str) -> jax.Array:
        """Combine per-device partial ``aggregate`` accumulators over a
        shard_map mesh axis. Because every codec's ``aggregate`` is a linear
        fp32 SUM over its client axis — bitpacked sign wires, COO scatters
        and dense einsums alike — the cross-device reduce is one O(d) psum
        of the accumulator (wire.psum_accumulator), NEVER a gather of the
        per-client payload stack. The multi-device device walk of
        ``fedavg.build_round_step`` calls this once per round, after each
        device's shard scan."""
        return wire.psum_accumulator(acc, axis_name)


# ---------------------------------------------------------------------------
# legacy shim: the monolithic compressor names, as pipeline factories
# ---------------------------------------------------------------------------

def Compressor(name: str = "identity") -> Pipeline:
    """Legacy identity compressor -> ``Pipeline(codec=DenseCodec())``."""
    return Pipeline((), DenseCodec(), name=name)


def ZSignCompressor(name: str = "zsign", z: int = 1, sigma: float = 0.01,
                    **kw) -> Pipeline:
    return Pipeline((), SignCodec(z=z, sigma=sigma, **kw), name=name)


def PackedZSignCompressor(name: str = "zsign_packed", z: int = 1,
                          sigma: float = 0.01,
                          encode_backend: str = "pallas", **kw) -> Pipeline:
    return Pipeline((), SignCodec(z=z, sigma=sigma, dense_kernel=True,
                                  encode_backend=encode_backend, **kw),
                    name=name)


def StoSignCompressor(name: str = "stosign", **kw) -> Pipeline:
    return Pipeline((), SignCodec(z=znoise.Z_INF, sigma_mode="norm", **kw),
                    name=name)


def EFSignCompressor(name: str = "efsign", use_kernel: bool = False,
                     **kw) -> Pipeline:
    return Pipeline((ErrorFeedback(),),
                    SignCodec(scale="mean_abs", use_kernel=use_kernel, **kw),
                    name=name)


def QSGDCompressor(name: str = "qsgd", s: int = 1) -> Pipeline:
    return Pipeline((), QSGDCodec(s=s), name=name)


def TopKCompressor(name: str = "topk", frac: float = 0.01,
                   chunk: int = 65536) -> Pipeline:
    return Pipeline((ErrorFeedback(),), TopKCodec(frac=frac, chunk=chunk),
                    name=name)


def DPGaussianCompressor(name: str = "dpgauss",
                         sigma: float = 1.0) -> Pipeline:
    return Pipeline((DPTransform(noise=sigma),), DenseCodec(), name=name)


_REGISTRY = {
    "identity": Compressor,
    "zsign": ZSignCompressor,
    "stosign": StoSignCompressor,
    "efsign": EFSignCompressor,
    "qsgd": QSGDCompressor,
    "topk": TopKCompressor,
    "dpgauss": DPGaussianCompressor,
    "zsign_packed": PackedZSignCompressor,
}


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
