"""z-SignFedAvg round engine (paper Algorithm 1, plus every baseline).

One round: every sampled client runs E local SGD steps from the server
params, flattens its pseudo-gradient (x0 - xE)/gamma ONCE into a 1-D fp32
wire buffer (core/wire.TreeSpec) and encodes it with the compression
Pipeline (core/compression.py: sign families ship bitpacked uint8, top-k
ships COO pairs, identity ships fp32); the server folds the payloads into
one running wire accumulator in the compressed domain, weighted by the
participation mask (robust ``agg=vote|trimmed|median`` modes carry the
int32 vote pair), then decodes the sum, unflattens ONCE and steps its
optimizer. Deployment policy (backends, the 0/1-mask guarantee, dynamic
sigma, the cohort plan, the adversary, the round mode) arrives as ONE typed
value, the RoundContext of core/context.py, applied to the pipeline at
build time.

ONE SHARD EXECUTOR (``_build_round_math``). Its step takes one shard of
clients: it derives their PRNG keys from the shard's GLOBAL client offset
(noise.client_keys, a counter derivation, so results do not depend on how
the cohort is partitioned), vmaps the local-SGD scan and the encode over
the shard, applies the participation mask to the per-client state, and
folds the shard's payload stack into the accumulator
(``Pipeline.aggregate(..., acc=...)``). Its accumulator init starts that
fold from the codec's ``fold_init`` carry or from zeros. Three drivers
walk the cohort through it:

  device walk   (``cohort=vmap|auto|stream``) the cohort's batch, mask and
                state live on the device, resharded into shard-client
                slices; one shard is one call, more are a ``lax.scan``
                whose carry is the accumulator, so a full-cohort payload
                stack never exists. ``vmap`` runs a (G, N) cohort as G
                shards of N; ``stream(shard=K)`` as shards of K. Peak
                memory is O(d) model + O(shard * E * batch) data +
                O(shard * d/8) wire for sign families, for ANY cohort size.
                ``stream(devices=D)`` splits the shard sequence into
                contiguous per-device slices over a 1-D ``clients`` mesh
                (``shard_map``); the per-device accumulators meet in ONE
                O(d) ``lax.psum`` (wire.psum_accumulator) before decode, so
                the interconnect never carries a payload stack. ``auto``
                (and a bare ``stream``) streams iff ``total_clients *
                n_coords`` reaches context.STREAM_AUTO_MIN_ELEMS.
  host walk     (``stream(feed=host)``) slices the cohort on the host and
                uploads shard t+1 while shard t computes, so only one
                shard of batch/mask/state is on the device. The round step
                is a Python loop: do not wrap it in jax.jit.
  async         (``round_mode=async(...)``, fed/async_server.py) the host
                walk with on-time fold weights and a queue of late
                payloads.

All plans and shard sizes are bit-identical for 0/1 masks (integer sign
sums), and for fp32-weighted (EF) folds through the ``wire.SignFoldAcc``
carry, which keeps the full call's 8-client block order; see
wire.unpack_sum. RoundContext.adversary applies mid-round dropout to the
slot mask at the top of the round and payload attacks inside the shard
step, selected by GLOBAL client index + round, so an attack is the same
under every plan.

Per-client compressor state (EF / top-k residuals) is a flat fp32 buffer of
shape (client_groups, n_clients, n_coords); dead clients keep their previous
residual bit-exactly (the state update is participation-masked). When the
cohort does not divide the shard size, the last shard is padded with
wrapped-around batch rows under a zero participation mask — padded slots
contribute exactly nothing and their state rows are discarded.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import noise as znoise
from repro.core import wire
from repro.core.context import (COHORT_DEVICES_AUTO, STREAM_AUTO_MIN_ELEMS,
                                STREAM_DEFAULT_SHARD, STREAM_SHARD_AUTO,
                                STREAM_SHARD_BUDGET_BYTES, STREAM_SHARD_MAX,
                                STREAM_SHARD_MIN, CohortPolicy, RoundContext,
                                RoundModePolicy)
from repro.core.dp import clip_flat
from repro.core.spans import phase
from repro.optim.optimizers import Optimizer, make_optimizer


@dataclasses.dataclass(frozen=True)
class FedConfig:
    n_clients: int = 8            # parallel clients (vmapped / mesh-sharded)
    client_groups: int = 1        # groups of n_clients, run as shards
    local_steps: int = 1          # E
    client_lr: float = 0.01       # gamma
    server_lr: float = 1.0        # eta (decode already applies eta_z * sigma)
    server_opt: str = "sgd"       # sgd | momentum | adam
    server_opt_kw: tuple = ()     # e.g. (("momentum", 0.9),)
    dp_clip: float = 0.0          # >0 enables DP-SignFedAvg clipping (Alg. 2)


class ServerState(NamedTuple):
    params: Any
    opt_state: Any
    #: stacked per-client state tree {slot: (G, N, ...)} or None
    comp_state: Any
    rng: jax.Array
    round: jax.Array      # int32 scalar
    sigma: jax.Array      # dynamic noise scale (Plateau criterion)
    #: SHARED server-scope pipeline state ({slot: (n_coords,)} control
    #: variates) or None. Defaulted LAST field: existing keyword
    #: constructions and old checkpoints stay valid.
    comp_server: Any = None


class RoundMetrics(NamedTuple):
    loss: jax.Array
    grad_est_norm: jax.Array
    participation: jax.Array
    uplink_bits: jax.Array
    #: clients per stream shard this round (0 on the vmap plan) — recorded so
    #: benchmark rows stay self-describing when the shard size is auto-tuned.
    #: Always a device int32 scalar: a host np.int32 default would silently
    #: type-promote when metrics from eager (host-fed) and jitted rounds are
    #: stacked across a buffered window (jnp.stack over mixed host/device
    #: scalars re-derives the dtype instead of keeping int32).
    shard_clients: jax.Array = jnp.asarray(0, jnp.int32)


class RoundMath(NamedTuple):
    """The shard executor every driver runs (see ``_build_round_math``).

    ``shard_step(spec, params, sub, sigma, round_idx, server, s_idx,
    batch_s, cstate_s, mask_s, fold_w, acc, loss_acc)``
        one shard of clients (leading axis = the mask length) -> (acc with
        the shard's payloads folded in at ``fold_w``, loss_acc + the
        shard's masked loss sum, participation-masked new state rows, the
        shard's payload stack). ``server`` is the SHARED server-scope
        pipeline state (ServerState.comp_server, e.g. the cv server
        variate): broadcast to every client, never sliced, updated only in
        the server finish.
    ``acc_init(spec, params, sigma, server, sub, batch_s, cstate_s,
    mask_s)``
        the accumulator a fold over shards shaped like these starts from.
    """
    shard_step: Callable
    acc_init: Callable


def init_server_state(params, cfg: FedConfig, compressor,
                      rng: jax.Array, sigma0: float = 0.0) -> ServerState:
    opt = _server_optimizer(cfg)
    spec = wire.tree_spec(params)
    cstate = compressor.init_state(spec.n_coords)
    if cstate is not None:
        # one flat state row per client per slot: (groups, n_clients, ...)
        cstate = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (cfg.client_groups, cfg.n_clients) + x.shape), cstate)
    # shared server-scope slots (control variates): ONE tree, no client axis
    cserver = compressor.init_server_state(spec.n_coords)
    return ServerState(params=params, opt_state=opt.init(params),
                       comp_state=cstate, rng=rng,
                       round=jnp.zeros((), jnp.int32),
                       sigma=jnp.asarray(sigma0, jnp.float32),
                       comp_server=cserver)


def _server_optimizer(cfg: FedConfig) -> Optimizer:
    return make_optimizer(cfg.server_opt, lr=cfg.server_lr, **dict(cfg.server_opt_kw))


class CohortPlan(NamedTuple):
    """Resolved execution plan of the round driver (see resolve_cohort)."""
    mode: str          # "vmap" | "stream"
    shard: int         # clients per stream shard (0 on the vmap plan)
    unroll: int        # lax.scan unroll of the shard loop
    devices: int       # size of the 'clients' shard_map mesh axis (1 = none)
    feed: str          # "device" | "host" shard feeding


#: the vmap plan — each client group is one shard, no device axis
VMAP_PLAN = CohortPlan("vmap", 0, 1, 1, "device")


def auto_shard_size(n_coords: int) -> int:
    """Pick the streaming shard size K from the model coordinate count and
    the per-device memory budget (context.STREAM_SHARD_BUDGET_BYTES).

    The streaming engine's per-shard working set is ~one dense f32 gradient
    per in-flight client plus its packed wire row (4*d + d/8 bytes each), so
    K = budget // (4*d + d/8), clamped to [STREAM_SHARD_MIN,
    STREAM_SHARD_MAX] and rounded down to a multiple of
    wire.SIGN_REDUCE_CLIENT_BLK. Block alignment is a throughput choice
    now, not a correctness one: the SignFoldAcc carry keeps fp32-weighted
    folds bit-reproducible at ANY shard size, but blk-aligned shards keep
    its pending-row buffer permanently empty.
    """
    if n_coords <= 0:
        return STREAM_DEFAULT_SHARD
    per_client = 4 * n_coords + n_coords // 8
    k = STREAM_SHARD_BUDGET_BYTES // per_client
    k = (k // wire.SIGN_REDUCE_CLIENT_BLK) * wire.SIGN_REDUCE_CLIENT_BLK
    return int(min(max(k, STREAM_SHARD_MIN), STREAM_SHARD_MAX))


def resolve_cohort(policy, total_clients: int,
                   n_coords: int) -> CohortPlan:
    """CohortPolicy (or its spec string) + static round shapes -> the
    driver's CohortPlan: ("vmap", 0, 1, 1, "device") or
    ("stream", shard, unroll, devices, feed).

    THE one place the streaming auto-gate lives: ``auto`` and a bare
    ``stream`` fall back to the vmap plan below STREAM_AUTO_MIN_ELEMS
    client-coordinate elements (below the measured scan-overhead crossover;
    see context.py), while an explicit ``stream(shard=K)``, ``shard=auto``,
    ``devices=`` or ``feed=host`` always streams — the bit-identity tests
    and memory pins force the path this way at small sizes. ``shard=0`` and
    ``shard=auto`` both take the memory-budget K of ``auto_shard_size``;
    the shard is clamped to the cohort. ``devices=auto`` expands to every
    local device; the resolved count is clamped to the shard count (no
    all-padding devices) and validated against jax.device_count().
    """
    pol = CohortPolicy.parse(policy)
    if pol.mode == "vmap":
        return VMAP_PLAN
    forced = pol.mode == "stream" and (pol.shard != 0 or pol.devices != 1
                                       or pol.feed == "host")
    if not forced and total_clients * n_coords < STREAM_AUTO_MIN_ELEMS:
        return VMAP_PLAN
    want = (auto_shard_size(n_coords)
            if pol.shard in (0, STREAM_SHARD_AUTO) else pol.shard)
    shard = min(want, total_clients)
    if shard >= total_clients and not forced:
        return VMAP_PLAN   # one shard of the whole cohort IS the vmap plan
    devices = pol.devices
    if devices == COHORT_DEVICES_AUTO:
        devices = jax.device_count()
    if devices > jax.device_count():
        raise ValueError(
            f"cohort plan wants devices={devices} but only "
            f"{jax.device_count()} are visible (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=D to "
            f"simulate a multi-device host on CPU)")
    devices = max(1, min(devices, -(-total_clients // shard)))
    return CohortPlan("stream", shard, pol.unroll, devices, pol.feed)


def iter_shards(batch, mask, cstate, *, shard: int, total: int):
    """Host-side shard feeder for ``stream(feed=host)``: yields one
    ``(s_idx, batch_s, cstate_s, mask_s)`` tuple of numpy slices per shard,
    in global shard order.

    The slices mirror the device-resident reshard of the device walk
    exactly — the final shard wrap-pads with the cohort's first rows under a
    zero participation mask, and ``s_idx`` is the GLOBAL shard index (a
    ``np.uint32`` scalar, so the jitted per-shard kernel traces once) — which
    is what makes the host-fed round bit-identical to the device-fed one.
    The host driver ``jax.device_put``s tuple t+1 while tuple t computes
    (double buffering), so only one shard of batch/mask/state occupies
    device memory at a time.
    """
    n_shards = -(-total // shard)
    flat = lambda x: np.asarray(x).reshape((total,) + np.shape(x)[2:])
    b = jax.tree.map(flat, batch)
    m = np.asarray(mask, dtype=np.float32).reshape(total)
    c = None if cstate is None else jax.tree.map(flat, cstate)
    for s in range(n_shards):
        sl = np.arange(s * shard, (s + 1) * shard)
        rows = sl % total
        take = lambda x: x[rows]
        yield (np.uint32(s),
               jax.tree.map(take, b),
               None if c is None else jax.tree.map(take, c),
               (m[rows] * (sl < total)).astype(np.float32))


def client_pseudo_gradient(loss_fn: Callable, cfg: FedConfig, spec, params0,
                           client_batch):
    """One client's E local SGD steps from ``params0`` -> (flat f32
    pseudo-gradient (x0 - xE)/gamma, mean loss). ``client_batch`` leaves
    lead with the E axis; ``spec`` is the params' wire.TreeSpec."""
    gamma = cfg.client_lr
    if cfg.local_steps == 1:
        # E == 1: the pseudo-gradient (x0 - x1)/gamma IS the batch
        # gradient, so neither the updated weights nor the subtraction
        # back need to exist (and a length-1 lax.scan would lower to an
        # XLA while loop whose params-tree carry is copied at the loop
        # boundary). Identical up to f32 rounding: this path skips the
        # (gamma*g)/gamma round-trip.
        with phase("fed.client.sgd"):
            loss, g = jax.value_and_grad(loss_fn)(
                params0, jax.tree.map(lambda x: x[0], client_batch))
        with phase("fed.client.flatten"):
            return spec.flatten(g), loss

    def step(p, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        p = jax.tree.map(lambda w, gw: w - gamma * gw.astype(w.dtype), p, g)
        return p, loss

    with phase("fed.client.sgd"):
        x_e, losses = jax.lax.scan(step, params0, client_batch)
        loss = jnp.mean(losses)
    with phase("fed.client.flatten"):
        pseudo = jax.tree.map(
            lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32))
            / gamma, params0, x_e)
        # the ONE flatten: pytree -> contiguous fp32 wire buffer
        return spec.flatten(pseudo), loss


def _build_round_math(loss_fn: Callable, compressor, cfg: FedConfig, *,
                      dynamic_sigma: bool, adversary=None) -> RoundMath:
    """Build the round-math half: per-shard client compute and the one
    shard executor, no scheduling.

    ``adversary`` is a bound fed/adversary.py policy (or None): payload
    attacks are injected in ``group_encode`` on the ENCODED wire stack —
    after the client encode, before aggregation and state masking — so an
    EF client's residual tracks what it MEANT to send (wire-transit
    corruption semantics) and every driver sees the identical attack
    (selection is by global client index + round).
    """
    def client_update(spec, params0, client_batch, key, cstate, sigma,
                      server=None):
        flat, loss = client_pseudo_gradient(loss_fn, cfg, spec, params0,
                                            client_batch)
        if cfg.dp_clip > 0.0:
            with phase("fed.client.flatten"):
                flat = clip_flat(flat, cfg.dp_clip)
        # only pipelines with server-scope slots receive ``server`` and
        # only tree-structured pipelines (sigma_sched) receive ``spec``
        with phase("fed.client.encode"):
            enc, new_cstate = compressor.encode(
                key, flat, cstate, sigma=sigma if dynamic_sigma else None,
                **({"server": server} if server is not None else {}),
                **({"spec": spec} if compressor.needs_tree_spec else {}))
        return enc, new_cstate, loss

    def group_encode(spec, params, group_batch, keys, group_cstate, mask_g,
                     sigma, idx_g=None, round_idx=None, server=None):
        """One shard of mask_g.shape[0] clients: returns the client-stacked
        payloads (NOT yet aggregated), the participation-masked new state,
        and the masked loss sum. ``idx_g`` is the shard's GLOBAL client
        indices and ``round_idx`` the traced round counter — only consumed
        by the adversary's payload injection (both optional: shape-probing
        eval_shape calls skip them; corruption never changes shapes).
        ``server`` is the shared server-scope pipeline state
        (ServerState.comp_server), broadcast — never sliced — across the
        shard's clients."""
        cu = lambda *a: client_update(spec, *a)
        if mask_g.shape[0] == 1:
            # a one-client shard runs the client step unbatched, so its
            # encode takes the kernels' one-client path and not their vmap
            # rule: the benchmark's cells all run stream(shard=1)
            enc1, ncs1, loss1 = cu(
                params, jax.tree.map(lambda x: x[0], group_batch), keys[0],
                (None if group_cstate is None
                 else jax.tree.map(lambda x: x[0], group_cstate)), sigma,
                server)
            enc = jax.tree.map(lambda e: e[None], enc1)
            new_cstate = (None if ncs1 is None
                          else jax.tree.map(lambda e: e[None], ncs1))
            losses = loss1[None]
        else:
            enc, new_cstate, losses = jax.vmap(
                cu,
                in_axes=(None, 0, 0,
                         0 if group_cstate is not None else None, None,
                         None),
            )(params, group_batch, keys, group_cstate, sigma, server)
        if adversary is not None and idx_g is not None:
            # wire-transit corruption: the payload stack is attacked AFTER
            # the honest encode (EF residuals above stay honest) and BEFORE
            # aggregation/state masking
            enc = adversary.corrupt(enc, idx_g, round_idx)
        # participation mask: dead clients contribute zero (weight 0 in the
        # aggregate); stateful compressors keep their residual bit-exactly.
        if group_cstate is not None:
            new_cstate = jax.tree.map(
                lambda new, old: jnp.where(
                    mask_g.reshape((-1,) + (1,) * (new.ndim - 1)) > 0, new, old),
                new_cstate, group_cstate)
        # dead (and shard-padding) clients are excluded via where, not just
        # the weight product, so a non-finite loss on an excluded slot can
        # never poison the round metric
        loss_sum = jnp.sum(jnp.where(mask_g > 0, losses * mask_g, 0.0))
        return enc, new_cstate, loss_sum

    def shard_step(spec, params, sub, sigma, round_idx, server, s_idx,
                   batch_s, cstate_s, mask_s, fold_w, acc, loss_acc):
        """THE per-shard step of every driver: one shard of clients through
        ``group_encode``, its payload stack folded into the running wire
        accumulator ``acc`` at weights ``fold_w`` (the mask, or the async
        driver's on-time weights). ``s_idx`` is the GLOBAL shard index:
        client keys derive from the shard's global offset (counter-based,
        so the key of client j never depends on the shard partition or
        device placement). Returns (acc, loss_acc + shard loss, new state
        rows, payload stack)."""
        shard = mask_s.shape[0]
        keys_s = znoise.client_keys(sub, s_idx * jnp.uint32(shard), shard)
        idx_s = (s_idx.astype(jnp.int32) * shard
                 + jnp.arange(shard, dtype=jnp.int32))
        enc, new_cstate_s, loss_s = group_encode(
            spec, params, batch_s, keys_s, cstate_s, mask_s, sigma, idx_s,
            round_idx, server)
        with phase("fed.server.fold"):
            acc = compressor.aggregate(enc, fold_w, spec.n_coords, acc=acc)
        return acc, loss_acc + loss_s, new_cstate_s, enc

    def acc_init(spec, params, sigma, server, sub, batch_s, cstate_s,
                 mask_s):
        """The wire accumulator a fold starts from, shaped by one shard's
        payload stack: fp32-weighted sign codecs hand back a structured
        wire.SignFoldAcc (the pending-row carry that makes the shard fold
        bit-identical to one concatenated reduce at ANY shard size); other
        routes start from zeros shaped by the codec's own aggregate."""
        enc_shape = jax.eval_shape(
            lambda b, k, c, m: group_encode(
                spec, params, b, k, c, m, sigma, server=server)[0],
            batch_s, znoise.client_keys(sub, 0, mask_s.shape[0]), cstate_s,
            mask_s)
        acc = compressor.fold_init(enc_shape)
        if acc is None:
            agg = jax.eval_shape(
                lambda e, m: compressor.aggregate(e, m, spec.n_coords),
                enc_shape, mask_s)
            acc = jnp.zeros(agg.shape, agg.dtype)
        return acc

    return RoundMath(shard_step=shard_step, acc_init=acc_init)


def build_round_step(loss_fn: Callable, compressor, cfg: FedConfig,
                     ctx: Optional[RoundContext] = None):
    """Returns round_step(state, batch, mask) -> (state, RoundMetrics): a
    driver of the shard executor (``_build_round_math``) chosen by ``ctx``.

    loss_fn(params, batch_slice) -> scalar loss. ``batch`` is a pytree whose
    leaves have leading dims (client_groups, n_clients, E, ...). ``mask`` is a
    float (client_groups, n_clients) participation mask (straggler dropout /
    partial participation); pass all-ones for full participation.

    ``compressor`` is a compression ``Pipeline``; ``ctx`` the typed
    deployment policy (core/context.py RoundContext, default
    ``RoundContext()``): backend selection for the client fused encode and
    the server sign-reduce, the static ``weights_are_mask`` 0/1 guarantee,
    ``dynamic_sigma``, the cohort plan (see the module docstring and
    ``resolve_cohort``), the adversary and the round mode. The engine
    applies the context to the pipeline ONCE here via
    ``Pipeline.with_context``, so kernels are dispatched per-stage.

    Per-client PRNG keys are derived by GLOBAL client index
    (noise.client_keys), so every plan and shard size consumes identical
    randomness.
    """
    ctx = RoundContext() if ctx is None else ctx
    compressor = compressor.with_context(ctx)
    cohort_policy = CohortPolicy.parse(ctx.cohort)
    opt = _server_optimizer(cfg)
    gamma = cfg.client_lr
    total = cfg.client_groups * cfg.n_clients
    adversary = None
    if ctx.adversary != "none":
        from repro.fed.adversary import parse_adversary
        adversary = parse_adversary(ctx.adversary)
        if adversary is not None:
            adversary = adversary.bind(total)
    math = _build_round_math(loss_fn, compressor, cfg,
                             dynamic_sigma=ctx.dynamic_sigma,
                             adversary=adversary)
    dynamic_sigma = ctx.dynamic_sigma

    def device_walk(spec, params, batch, mask, cstate, sub, sigma,
                    round_idx, plan: CohortPlan, server=None):
        """The device-resident driver: reshard the flat cohort into
        shard-client slices and fold them through the shard step — one
        call when there is one shard, a ``lax.scan`` otherwise, so the
        full-cohort payload stack never exists (the scan carry is the
        aggregate's own output buffer, O(d/8) bytes for sign wires). The
        vmap plan runs the (G, N) cohort as G shards of N.

        With ``plan.devices > 1`` the shard sequence is split into
        contiguous per-device slices over a 1-D ``clients`` mesh
        (shard_map): each device runs the identical scan on its slice
        (shard indices stay GLOBAL, so the counter-based key derivation is
        placement-invariant) and the local fp32 accumulators meet in one
        O(d) psum — the only cross-device collective of the round."""
        shard = plan.shard if plan.mode == "stream" else cfg.n_clients
        devices = plan.devices
        n_shards = -(-total // shard)
        if devices > 1:
            # pad the shard count so each device scans an equal slice;
            # all-pad shards carry a zero mask and contribute exactly 0
            n_shards = -(-n_shards // devices) * devices
        slots = n_shards * shard
        pad = slots - total

        def reshard(x):
            # (G, N, ...) -> (n_shards, shard, ...); padded slots wrap to
            # the cohort's first rows (real, finite data) under a zero
            # mask, so padding contributes exactly 0. Cyclic gather rather
            # than jnp.pad(mode="wrap"): device padding can exceed one
            # period of a small cohort.
            y = x.reshape((total,) + x.shape[2:])
            if pad:
                y = jnp.take(y, jnp.arange(slots) % total, axis=0)
            return y.reshape((n_shards, shard) + y.shape[1:])

        s_batch = jax.tree.map(reshard, batch)
        s_mask = reshard(mask) * (jnp.arange(slots)
                                  .reshape(n_shards, shard) < total)
        s_cstate = (None if cstate is None
                    else jax.tree.map(reshard, cstate))
        s_idx = jnp.arange(n_shards, dtype=jnp.uint32)
        shard0 = lambda t: (None if t is None
                            else jax.tree.map(lambda x: x[0], t))
        acc0 = math.acc_init(spec, params, sigma, server, sub,
                             shard0(s_batch), shard0(s_cstate), s_mask[0])

        def scan_shards(params_d, sub_d, sigma_d, round_d, server_d, idx_d,
                        batch_d, cstate_d, mask_d, acc_d):
            def body(carry, xs):
                acc, loss_acc = carry
                g_idx, batch_s, cstate_s, mask_s = xs
                acc, loss_acc, new_cstate_s, _ = math.shard_step(
                    spec, params_d, sub_d, sigma_d, round_d, server_d,
                    g_idx, batch_s, cstate_s, mask_s, mask_s, acc, loss_acc)
                return (acc, loss_acc), new_cstate_s

            return jax.lax.scan(body, (acc_d, jnp.zeros(())),
                                (idx_d, batch_d, cstate_d, mask_d),
                                unroll=plan.unroll)

        if devices <= 1:
            if n_shards == 1:
                enc_sum, loss_sum, cstate_1, _ = math.shard_step(
                    spec, params, sub, sigma, round_idx, server, s_idx[0],
                    shard0(s_batch), shard0(s_cstate), s_mask[0], s_mask[0],
                    acc0, jnp.zeros(()))
                cstate_sh = (None if cstate_1 is None
                             else jax.tree.map(lambda x: x[None], cstate_1))
            else:
                (enc_sum, loss_sum), cstate_sh = scan_shards(
                    params, sub, sigma, round_idx, server, s_idx, s_batch,
                    s_cstate, s_mask, acc0)
            with phase("fed.server.fold"):
                enc_sum = compressor.fold_finalize(enc_sum)
        else:
            mesh = Mesh(np.asarray(jax.devices()[:devices]), ("clients",))
            rep, shd = P(), P("clients")

            def per_device(params_d, sub_d, sigma_d, round_d, server_d,
                           acc_d, idx_d, batch_d, cstate_d, mask_d):
                (acc, loss), cstate_out = scan_shards(
                    params_d, sub_d, sigma_d, round_d, server_d, idx_d,
                    batch_d, cstate_d, mask_d, acc_d)
                # structured fold carries finalize BEFORE the psum: pending
                # rows are positional, not additive, and the flat fp32
                # buffer keeps the collective at one O(d) psum
                with phase("fed.server.fold"):
                    acc = compressor.fold_finalize(acc)
                # THE cross-device reduce: one O(<= 2d) psum of the local
                # wire accumulators (f32 sum, or the int32 vote pair for
                # robust agg=) — compressed-domain all the way; the
                # per-client payload stack never crosses the interconnect
                with phase("fed.server.psum"):
                    acc = compressor.reduce_across_devices(acc, "clients")
                    loss = jax.lax.psum(loss, "clients")
                return acc, loss, cstate_out

            enc_sum, loss_sum, cstate_sh = jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(rep, rep, rep, rep, rep, rep, shd, shd, shd, shd),
                out_specs=(rep, rep, shd),
                check_vma=False,
            )(params, sub, sigma, jnp.asarray(round_idx, jnp.int32),
              server, acc0, s_idx, s_batch, s_cstate, s_mask)
        if cstate_sh is None:
            new_cstate = None
        else:
            new_cstate = jax.tree.map(
                lambda x: x.reshape((slots,) + x.shape[2:])
                [:total].reshape((cfg.client_groups, cfg.n_clients)
                                 + x.shape[2:]),
                cstate_sh)
        return enc_sum, new_cstate, loss_sum

    def round_step(state: ServerState, batch, mask):
        spec = wire.tree_spec(state.params)
        rng, sub = jax.random.split(state.rng)
        sigma = state.sigma
        plan = resolve_cohort(cohort_policy, total, spec.n_coords)
        if adversary is not None:
            # mid-round dropout fires on the FULL slot mask before anything
            # else, so n_live, loss weighting and state masking all agree
            mask = adversary.drop_mask(jnp.asarray(mask, jnp.float32),
                                       state.round)
        enc_sum, new_cstate, loss_sum = device_walk(
            spec, state.params, batch, mask, state.comp_state, sub, sigma,
            state.round, plan, server=state.comp_server)
        return _finish(state, spec, rng, sigma, enc_sum, new_cstate,
                       loss_sum, mask, plan.shard)

    @phase("fed.server.apply")
    def _finish(state, spec, rng, sigma, enc_sum, new_cstate, loss_sum,
                mask, shard_used):
        n_live = jnp.maximum(jnp.sum(mask), 1.0)
        spec_kw = {"spec": spec} if compressor.needs_tree_spec else {}
        # the codec owns the full sum -> estimate mapping (robust agg=
        # modes decode the int32 vote pair; mean laws divide by n_live)
        g_flat = compressor.decode_sum(
            enc_sum, n_live, sigma=sigma if dynamic_sigma else None,
            **spec_kw)
        # the ONE unflatten: decoded flat estimate -> params-shaped pytree
        g_hat = spec.unflatten(g_flat)
        # Algorithm 1 line 15: x_t = x_{t-1} - eta * gamma * mean(Delta)
        scaled = jax.tree.map(lambda g: gamma * g, g_hat)
        new_params, new_opt = opt.update(scaled, state.opt_state, state.params)

        # server-scope pipeline state (control variates): fold the decoded
        # mean into the shared variate — exact for mean-law codecs because
        # g_flat is the mean of the per-client local decodes (the same
        # quantity each client folded into its own row this round)
        comp_server = state.comp_server
        if comp_server is not None:
            comp_server = compressor.update_server(
                comp_server, g_flat, n_live, float(total))

        metrics = RoundMetrics(
            loss=loss_sum / n_live,
            grad_est_norm=jnp.linalg.norm(g_flat[:spec.n_coords]),
            participation=n_live,
            uplink_bits=n_live * float(spec.n_coords
                                       * compressor.wire_bits_per_coord),
            shard_clients=jnp.asarray(shard_used, jnp.int32))
        new_state = ServerState(params=new_params, opt_state=new_opt,
                                comp_state=new_cstate, rng=rng,
                                round=state.round + 1, sigma=sigma,
                                comp_server=comp_server)
        return new_state, metrics

    # one jitted shard step per (shard, n_coords), cached across rounds;
    # s_idx arrives as a traced uint32 scalar so every shard reuses it
    shard_fns = {}

    def host_walk(state, spec, sub, batch, mask, shard, fold_w=None,
                  on_payload=None):
        """The double-buffered host driver: slice the cohort on the host
        (``iter_shards``), upload shard s+1 while shard s computes, and
        fold each shard through the jitted shard step — bit-identical to
        the device walk (same shard slices, same global-index keys, same
        left-fold order). ``fold_w`` (flat, padded to whole shards)
        replaces the mask as the fold weights; ``on_payload(lo, enc)``
        sees each shard's payload stack. Returns (unfinalized acc, loss
        sum, new client state)."""
        key = (shard, spec.n_coords)
        if key not in shard_fns:
            shard_fns[key] = jax.jit(
                lambda *a: math.shard_step(spec, *a))
        fn = shard_fns[key]
        n_shards = -(-total // shard)
        gen = iter_shards(batch, mask, state.comp_state, shard=shard,
                          total=total)
        cur = jax.device_put(next(gen))
        acc = math.acc_init(spec, state.params, state.sigma,
                            state.comp_server, sub, cur[1], cur[2], cur[3])
        loss_sum = jnp.zeros(())
        rows_host, prev_rows = [], None
        for s in range(n_shards):
            # double buffer: upload shard s+1 (async dispatch) before
            # launching shard s's compute ...
            nxt = jax.device_put(next(gen)) if s + 1 < n_shards else None
            w_s = (cur[3] if fold_w is None
                   else jnp.asarray(fold_w[s * shard:(s + 1) * shard]))
            acc, loss_sum, rows, enc = fn(
                state.params, sub, state.sigma, state.round,
                state.comp_server, *cur, w_s, acc, loss_sum)
            # ... and drain shard s-1's finished state rows to host while
            # shard s computes, so only one shard's tensors stay on device
            if state.comp_state is not None and prev_rows is not None:
                rows_host.append(jax.tree.map(np.asarray, prev_rows))
            prev_rows = rows
            if on_payload is not None:
                on_payload(s * shard, enc)
            cur = nxt
        new_cstate = None
        if state.comp_state is not None:
            rows_host.append(jax.tree.map(np.asarray, prev_rows))
            stacked = jax.tree.map(lambda *rs: np.concatenate(rs, axis=0),
                                   *rows_host)
            new_cstate = jax.tree.map(
                lambda x: x[:total].reshape(
                    (cfg.client_groups, cfg.n_clients) + x.shape[1:]),
                stacked)
        return acc, loss_sum, new_cstate

    def host_round_step(state: ServerState, batch, mask):
        """Python-loop round driver for ``stream(feed=host)`` — do NOT wrap
        in jax.jit (it slices host numpy per shard)."""
        spec = wire.tree_spec(state.params)
        plan = resolve_cohort(cohort_policy, total, spec.n_coords)
        rng, sub = jax.random.split(state.rng)
        if adversary is not None:
            # eager host step: materialize the dropped mask before slicing
            mask = np.asarray(adversary.drop_mask(
                jnp.asarray(mask, jnp.float32), state.round))
        acc, loss_sum, new_cstate = host_walk(state, spec, sub, batch, mask,
                                              plan.shard)
        return _finish(state, spec, rng, state.sigma,
                       compressor.fold_finalize(acc), new_cstate, loss_sum,
                       jnp.asarray(mask), plan.shard)

    # ---- round_mode=async(...): the deadline-fold driver ----------------
    mode_policy = RoundModePolicy.parse(ctx.round_mode)
    if mode_policy.mode == "async":
        # the async driver walks the cohort with host_walk and closes with
        # _finish, so its shard pass is the sync host driver's computation
        # exactly (the zero-latency bit-identity pin of
        # tests/test_async_server.py)
        from repro.fed.async_server import build_async_round_step
        return build_async_round_step(
            policy=mode_policy, latency_spec=ctx.latency,
            compressor=compressor, host_walk=host_walk,
            finish=_finish, cohort_policy=cohort_policy,
            adversary=adversary, total=total)

    return host_round_step if cohort_policy.feed == "host" else round_step


def make_batch_spec(cfg: FedConfig, per_step_batch: dict) -> dict:
    """Shape helper: expand a single-step batch spec to the round layout
    (groups, n_clients, E, ...)."""
    lead = (cfg.client_groups, cfg.n_clients, cfg.local_steps)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype), per_step_batch)
