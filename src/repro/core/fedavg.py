"""z-SignFedAvg round engine (paper Algorithm 1, plus every baseline).

One *round step* is a single jitted function:

    broadcast server params -> vmap over parallel clients:
        scan over E local SGD steps -> pseudo-gradient (x0 - xE)/gamma
        -> flatten ONCE to a 1-D fp32 wire buffer (core/wire.TreeSpec)
        -> compressor.encode  (the bitpacked 1-bit uplink payload)
    -> participation-masked flat aggregation over the client axis
       (uint8 collective + fused weighted sign-reduce == the compressed
       all-reduce; sign families never re-inflate the dense sign matrix;
       robust ``agg=vote|trimmed|median`` modes carry the int32 vote pair)
    -> compressor.decode_sum -> unflatten ONCE -> server optimizer update.

RoundContext.adversary threads a wire-level fault-injection policy
(fed/adversary.py) through every cohort plan: mid-round dropout is applied
to the slot mask at the top of the round; payload attacks (sign-flip, byte
corruption, collusion) hit each shard's encoded uint8 stack inside
``group_encode``, selected by GLOBAL client index + round counter so the
attack is bit-identical under vmap, stream(shard=K) and stream(devices=D).

The engine never touches per-leaf encodings: every compression Pipeline
(core/compression.py) speaks the flat wire-buffer codec of core/wire.py, so
there are no compressor-specific branches here — sign families ship
bitpacked uint8, top-k ships COO pairs, identity ships fp32, all through the
same four calls. Deployment policy (backend selection, mask guarantees,
dynamic sigma, legacy paths, cohort execution) arrives as ONE typed value —
the RoundContext of core/context.py — applied to the pipeline at build time.

The engine is split in two halves:

ROUND MATH (``_build_round_math``) — per-shard client compute: the local-SGD
scan, the fused encode, and the participation-masked state update for one
slice of clients, vmapped over that slice's leading axis. Pure in the shard:
it never knows how many shards exist or how they are scheduled.

ROUND DRIVER (``build_round_step``) — shard scheduling and slicing: derives
per-client PRNG keys by GLOBAL client index (noise.client_keys — a counter
derivation, so results are invariant to how the cohort is partitioned),
slices batch/mask/state per shard, and aggregates. ``RoundContext.cohort``
picks the walk:

  ``vmap``    one vmap over all ``n_clients`` parallel clients; sequential
              client *groups* are an outer ``lax.scan``. For compressed wire
              layouts the scan emits the raw payload stack as its OUTPUT and
              the server runs ONE ``aggregate`` over the (client_groups *
              n_clients, n_bytes) stack; dense fp32 layouts accumulate the
              decoded group sums in the scan carry (the choice is the
              compressor's ``stacks_group_payloads()``).
  ``stream``  the massive-cohort executor: the flat cohort of
              ``client_groups * n_clients`` clients is resharded into
              ``shard``-client slices and scanned, folding each shard's
              payload stack into ONE running wire accumulator via
              ``Pipeline.aggregate(..., acc=...)`` (reduce-as-you-go — a
              full-cohort payload stack never exists). Peak memory is O(d)
              model + O(shard * E * batch) data + O(shard * d/8) wire for
              sign families (one (d,) f32 carry for dense codecs), for ANY
              cohort size. Bit-identical to the vmap path at ANY shard
              size: 0/1-mask sign sums are integer-exact, and fp32-weighted
              (EF) aggregation streams through a ``wire.SignFoldAcc``
              carry (``Pipeline.fold_init``) that preserves the full
              call's 8-client block order; see wire.unpack_sum.

              ``stream(devices=D)`` adds the cross-DEVICE axis: the shard
              sequence is partitioned into contiguous per-device slices
              over a 1-D ``clients`` mesh (``shard_map``); every device
              runs the same shard scan on its slice, folding into its own
              local wire accumulator, and the accumulators meet in ONE
              ``lax.psum`` (wire.psum_accumulator) before decode — the
              cross-device reduce stays in the compressed-sum domain, so
              per-device interconnect traffic is O(d) fp32 regardless of
              cohort size (never a payload stack, never per-client data).
              Model params are replicated; batch/mask/EF-state shards are
              device-local; the per-client EF residuals come back sharded
              along the cohort axis. Counter-based client keys make the
              bits invariant to device placement, so D in {1..} produces
              bit-identical rounds for 0/1 masks at any shard size.

              ``stream(feed=host)`` swaps the device-resident shard tensor
              for a host-side double-buffered feeder (``iter_shards`` +
              async ``jax.device_put`` of shard t+1 while shard t
              computes): only ONE shard of batch/mask/state lives on
              device at a time, for cohorts whose round tensors exceed
              device memory. The returned round step is a Python loop —
              do not wrap it in jax.jit.
  ``auto``    stream iff ``total_clients * n_coords`` reaches
              context.STREAM_AUTO_MIN_ELEMS — small rounds keep the vmap
              path (measured on XLA CPU the shard lax.scan costs only
              ~0.1-0.2 ms/shard of loop overhead and the plans are within
              ~5% for unpacked wires; see the constant's docstring for the
              numbers), huge cohorts get the O(wire) memory contract. A
              bare ``stream`` gates the same way; ``stream(shard=K)`` /
              ``devices=`` / ``feed=host`` force.

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
    client_groups: int = 1        # sequential groups; total clients = n*groups
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
    """The round-MATH half of the engine: client compute for ONE shard.

    ``client_update(spec, params0, client_batch, key, cstate, sigma,
    server)``
        one client: local SGD -> flatten -> encode.
    ``group_encode(spec, params, batch, keys, cstate, mask, sigma, ...,
    server=None)``
        one shard of clients (leading axis = the mask length, vmapped):
        -> (stacked payloads, participation-masked new state, masked loss
        sum). The shard width is whatever the driver slices — a parallel
        group on the vmap path, ``shard_clients`` on the streaming path.
        ``server`` is the SHARED server-scope pipeline state
        (ServerState.comp_server, e.g. the cv server variate) — broadcast
        to every client, never sliced, updated only in the server finish.
    ``group_round(...)``
        group_encode + masked aggregation to one flat f32 SUM buffer.
    """
    client_update: Callable
    group_encode: Callable
    group_round: Callable


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
    cserver = (compressor.init_server_state(spec.n_coords)
               if hasattr(compressor, "init_server_state") else None)
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


#: the vmap plan — one vmap over the whole cohort, no device axis
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


def resolve_cohort(policy, total_clients: int, n_coords: int,
                   spmd_axes=None) -> CohortPlan:
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

    ``spmd_axes`` is the launcher's client-axis mesh sharding (dryrun /
    multi-chip plans): when set, the client axis is already parallelized by
    the surrounding mesh, so ``auto`` resolves to the vmap plan (the shard
    scan would SERIALIZE the sharded axis and force XLA into involuntary
    rematerializations) and a forced stream policy is a config conflict —
    the streaming cohort's own device axis is ``stream(devices=D)``.
    """
    pol = CohortPolicy.parse(policy)
    if pol.mode == "vmap":
        return VMAP_PLAN
    forced = pol.mode == "stream" and (pol.shard != 0 or pol.devices != 1
                                       or pol.feed == "host")
    if spmd_axes is not None:
        if forced:
            raise ValueError(
                f"cohort policy {policy!r} forces the streaming plan, "
                f"but the launcher plan shards the client axis over mesh "
                f"axes {spmd_axes!r} — the shard scan would serialize the "
                "axis the mesh parallelizes. Drop the stream(...) policy "
                "(the mesh already provides client parallelism) or use a "
                "launcher plan without client_axes.")
        return VMAP_PLAN
    if not forced and total_clients * n_coords < STREAM_AUTO_MIN_ELEMS:
        return VMAP_PLAN
    want = (auto_shard_size(n_coords)
            if pol.shard in (0, STREAM_SHARD_AUTO) else pol.shard)
    shard = min(want, total_clients)
    if shard >= total_clients and not forced:
        return VMAP_PLAN   # one shard IS the vmap path, minus the scan
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

    The slices mirror the device-resident reshard of ``stream_cohort``
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
                           client_batch, *, legacy_client_path: bool = False):
    """One client's E local SGD steps from ``params0`` -> (flat f32
    pseudo-gradient (x0 - xE)/gamma, mean loss). ``client_batch`` leaves
    lead with the E axis; ``spec`` is the params' wire.TreeSpec."""
    gamma = cfg.client_lr
    if cfg.local_steps == 1 and not legacy_client_path:
        # E == 1: the pseudo-gradient (x0 - x1)/gamma IS the batch
        # gradient, so neither the updated weights nor the subtraction
        # back need to exist (and a length-1 lax.scan would lower to an
        # XLA while loop whose params-tree carry is copied at the loop
        # boundary — an (n_clients x params) copy per round for zero
        # sequencing). ~2x less client-side memory traffic around the
        # flatten on the CPU benchmark; identical up to f32 rounding
        # (this path skips the (gamma*g)/gamma round-trip).
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
                      dynamic_sigma: bool, legacy_client_path: bool,
                      spmd_axes, constrain_wire: Callable,
                      adversary=None) -> RoundMath:
    """Build the round-math half: per-shard client compute, no scheduling.

    ``adversary`` is a bound fed/adversary.py policy (or None): payload
    attacks are injected in ``group_encode`` on the ENCODED wire stack —
    after the client encode, before aggregation and state masking — so an
    EF client's residual tracks what it MEANT to send (wire-transit
    corruption semantics) and every cohort plan sees the identical attack
    (selection is by global client index + round).
    """
    def client_update(spec, params0, client_batch, key, cstate, sigma,
                      server=None):
        flat, loss = client_pseudo_gradient(
            loss_fn, cfg, spec, params0, client_batch,
            legacy_client_path=legacy_client_path)
        if cfg.dp_clip > 0.0:
            with phase("fed.client.flatten"):
                flat = clip_flat(flat, cfg.dp_clip)
        # the server/spec kwargs are capability-gated: only pipelines with
        # server-scope slots receive ``server`` and only tree-structured
        # pipelines (sigma_sched) receive ``spec`` (legacy duck-typed
        # compressors keep their three-argument encode signature)
        with phase("fed.client.encode"):
            enc, new_cstate = compressor.encode(
                key, flat, cstate, sigma=sigma if dynamic_sigma else None,
                **({"server": server} if server is not None else {}),
                **({"spec": spec}
                   if getattr(compressor, "needs_tree_spec", False) else {}))
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
            # sequential-client (big-arch) mode: skip the vmap — a size-1
            # vmap without spmd_axis_name drops every sharding constraint
            # inside (measured: 16 TB/dev of replicate-fallback collectives
            # on jamba; EXPERIMENTS.md §Perf).
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
                spmd_axis_name=spmd_axes,
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

    def group_round(spec, params, group_batch, keys, group_cstate, mask_g,
                    sigma, idx_g=None, round_idx=None, server=None):
        """group_encode + masked aggregation to one flat SUM accumulator."""
        enc, new_cstate, loss_sum = group_encode(
            spec, params, group_batch, keys, group_cstate, mask_g, sigma,
            idx_g, round_idx, server)
        with phase("fed.server.fold"):
            enc_sum = constrain_wire(
                compressor.aggregate(enc, mask_g, spec.n_coords))
        return enc_sum, new_cstate, loss_sum

    return RoundMath(client_update=client_update, group_encode=group_encode,
                     group_round=group_round)


def build_round_step(loss_fn: Callable, compressor, cfg: FedConfig,
                     ctx: Optional[RoundContext] = None,
                     *, dynamic_sigma: bool = False,
                     param_constraint: Optional[Callable] = None,
                     wire_constraint: Optional[Callable] = None,
                     spmd_axes=None, agg_backend: Optional[str] = None,
                     encode_backend: Optional[str] = None,
                     weights_are_mask: bool = False,
                     legacy_client_path: bool = False):
    """Returns round_step(state, batch, mask) -> (state, RoundMetrics) —
    the round DRIVER (shard scheduling + key/batch/mask slicing) wrapped
    around the round math of ``_build_round_math``.

    loss_fn(params, batch_slice) -> scalar loss. ``batch`` is a pytree whose
    leaves have leading dims (client_groups, n_clients, E, ...). ``mask`` is a
    float (client_groups, n_clients) participation mask (straggler dropout /
    partial participation); pass all-ones for full participation.

    ``ctx`` is the typed deployment policy (core/context.py RoundContext):
    backend selection for the client fused encode and the server
    sign-reduce (``None`` keeps each stage's own setting), the static
    ``weights_are_mask`` 0/1 guarantee that unlocks the popcount
    aggregation specialization (leave False for fractional data-size
    weights), ``dynamic_sigma`` (thread the server state's traced Plateau
    sigma into the codec), ``legacy_client_path`` (restore the
    pre-fused client step — always scan over E local steps, even E == 1,
    and form the pseudo-gradient by updating the weights and subtracting
    them back — kept ONLY so the benchmark's dense baseline measures what
    the legacy round actually cost), and ``cohort`` (the execution plan:
    vmap vs the streaming massive-cohort shard scan; see the module
    docstring and ``resolve_cohort``). The engine applies the context to the
    compression pipeline ONCE here via ``Pipeline.with_context``, so kernels
    are dispatched per-stage. The keyword arguments after ``ctx`` mirror the
    pre-RoundContext API and are folded into a context when ``ctx`` is not
    given; new callers should pass a RoundContext.

    Per-client PRNG keys are derived by GLOBAL client index
    (noise.client_keys), so the vmap and streaming paths — and any shard
    size — consume identical randomness.

    ``param_constraint`` re-applies sharding constraints to params-shaped
    trees inside the step (set by the launcher). ``wire_constraint`` pins the
    aggregated flat wire buffer — the launcher passes replicate (it is 8-32x
    smaller than the params and feeds one collective) so the unflatten back
    to sharded parameter layouts is a local slice, never a reshard (see
    launch/sharding.py wire_state_specs for the per-client residual layout).
    """
    legacy_kw = dict(agg_backend=agg_backend, encode_backend=encode_backend,
                     weights_are_mask=weights_are_mask,
                     legacy_client_path=legacy_client_path,
                     dynamic_sigma=dynamic_sigma)
    if ctx is None:
        ctx = RoundContext(**legacy_kw)
    elif any(v not in (None, False) for v in legacy_kw.values()):
        raise ValueError(
            "pass the round policy either as a RoundContext or as the "
            "legacy keyword arguments, not both — the kwargs set here "
            f"would be silently ignored: "
            f"{ {k: v for k, v in legacy_kw.items() if v not in (None, False)} }")
    if hasattr(compressor, "with_context"):
        compressor = compressor.with_context(ctx)
    else:
        # duck-typed legacy compressor objects: replace matching fields
        fields = {f.name for f in dataclasses.fields(compressor)}
        overrides = {k: v for k, v in [("agg_backend", ctx.agg_backend),
                                       ("encode_backend", ctx.encode_backend)]
                     if v is not None and k in fields}
        if ctx.weights_are_mask and "weights_are_mask" in fields:
            overrides["weights_are_mask"] = True
        if overrides:
            compressor = dataclasses.replace(compressor, **overrides)
    cohort_policy = CohortPolicy.parse(ctx.cohort)
    opt = _server_optimizer(cfg)
    gamma = cfg.client_lr
    constrain = param_constraint or (lambda t: t)
    constrain_wire = wire_constraint or (lambda f: f)
    total = cfg.client_groups * cfg.n_clients
    adversary = None
    if getattr(ctx, "adversary", "none") != "none":
        from repro.fed.adversary import parse_adversary
        adversary = parse_adversary(ctx.adversary)
        if adversary is not None:
            adversary = adversary.bind(total)
    math = _build_round_math(
        loss_fn, compressor, cfg, dynamic_sigma=ctx.dynamic_sigma,
        legacy_client_path=ctx.legacy_client_path, spmd_axes=spmd_axes,
        constrain_wire=constrain_wire, adversary=adversary)
    dynamic_sigma = ctx.dynamic_sigma

    def stream_cohort(spec, params, batch, mask, cstate, sub, sigma,
                      round_idx, shard: int, unroll: int, devices: int = 1,
                      server=None):
        """The streaming massive-cohort executor: reshard the flat cohort
        into ``shard``-client slices, lax.scan them through the round math,
        and FOLD each shard's payload stack into one running wire
        accumulator — the full-cohort stack never exists; the scan carry is
        the aggregate's own output buffer (O(d/8) bytes for sign wires).

        With ``devices > 1`` the shard sequence is split into contiguous
        per-device slices over a 1-D ``clients`` mesh (shard_map): each
        device runs the identical scan on its slice (shard indices stay
        GLOBAL, so the counter-based key derivation is placement-invariant)
        and the local fp32 accumulators meet in one O(d) psum — the only
        cross-device collective of the round."""
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

        # wire accumulator init: fp32-weighted sign codecs hand back a
        # structured wire.SignFoldAcc (pending-row carry that makes the
        # shard fold bit-identical to one concatenated reduce at ANY shard
        # size); other routes fall back to a zero buffer shaped by the
        # codec's own aggregate output
        enc_shape = jax.eval_shape(
            lambda b, k, c, m: math.group_encode(
                spec, params, b, k, c, m, sigma, server=server)[0],
            shard0(s_batch), znoise.client_keys(sub, 0, shard),
            shard0(s_cstate), s_mask[0])
        fold0 = (compressor.fold_init(enc_shape)
                 if hasattr(compressor, "fold_init") else None)
        if fold0 is None:
            agg_shape = jax.eval_shape(
                lambda e, m: compressor.aggregate(e, m, spec.n_coords),
                enc_shape, s_mask[0])
        finalize = (compressor.fold_finalize
                    if hasattr(compressor, "fold_finalize")
                    else (lambda a: a))

        def scan_shards(params_d, sub_d, sigma_d, round_d, server_d, idx_d,
                        batch_d, cstate_d, mask_d, constrain_acc):
            acc0 = (fold0 if fold0 is not None
                    else jnp.zeros(agg_shape.shape, agg_shape.dtype))

            def body(carry, xs):
                acc, loss_acc = carry
                g_idx, batch_s, cstate_s, mask_s = xs
                # per-shard keys from the shard's GLOBAL client offset: the
                # derivation is counter-based, so the key of client j never
                # depends on the shard partition or device placement
                # (bit-identity vs vmap and vs any device count)
                keys_s = znoise.client_keys(sub_d,
                                            g_idx * jnp.uint32(shard),
                                            shard)
                idx_s = (g_idx.astype(jnp.int32) * shard
                         + jnp.arange(shard, dtype=jnp.int32))
                enc, new_cstate_s, loss_s = math.group_encode(
                    spec, params_d, batch_s, keys_s, cstate_s, mask_s,
                    sigma_d, idx_s, round_d, server_d)
                with phase("fed.server.fold"):
                    acc = compressor.aggregate(enc, mask_s, spec.n_coords,
                                               acc=acc)
                if fold0 is None:
                    # launcher wire constraints expect the flat buffer;
                    # the structured carry is constrained post-finalize
                    acc = constrain_acc(acc)
                return (acc, loss_acc + loss_s), new_cstate_s

            return jax.lax.scan(body, (acc0, jnp.zeros(())),
                                (idx_d, batch_d, cstate_d, mask_d),
                                unroll=unroll)

        if devices <= 1:
            (enc_sum, loss_sum), cstate_sh = scan_shards(
                params, sub, sigma, round_idx, server, s_idx, s_batch,
                s_cstate, s_mask, constrain_wire)
            if fold0 is not None:
                with phase("fed.server.fold"):
                    enc_sum = constrain_wire(finalize(enc_sum))
        else:
            mesh = Mesh(np.asarray(jax.devices()[:devices]), ("clients",))
            rep, shd = P(), P("clients")

            def per_device(params_d, sub_d, sigma_d, round_d, server_d,
                           idx_d, batch_d, cstate_d, mask_d):
                # launcher wire constraints name OUTER mesh axes — they
                # cannot apply inside the shard body; the post-psum result
                # is constrained by the caller instead
                (acc, loss), cstate_out = scan_shards(
                    params_d, sub_d, sigma_d, round_d, server_d, idx_d,
                    batch_d, cstate_d, mask_d, lambda a: a)
                # structured fold carries finalize BEFORE the psum: pending
                # rows are positional, not additive, and the flat fp32
                # buffer keeps the collective at one O(d) psum
                with phase("fed.server.fold"):
                    acc = finalize(acc)
                # THE cross-device reduce: one O(<= 2d) psum of the local
                # wire accumulators (f32 sum, or the int32 vote pair for
                # robust agg=) — compressed-domain all the way; the
                # per-client payload stack never crosses the interconnect
                with phase("fed.server.psum"):
                    if hasattr(compressor, "reduce_across_devices"):
                        acc = compressor.reduce_across_devices(acc,
                                                               "clients")
                    else:
                        acc = wire.psum_accumulator(acc, "clients")
                    loss = jax.lax.psum(loss, "clients")
                return acc, loss, cstate_out

            enc_sum, loss_sum, cstate_sh = jax.shard_map(
                per_device, mesh=mesh,
                in_specs=(rep, rep, rep, rep, rep, shd, shd, shd, shd),
                out_specs=(rep, rep, shd),
                check_vma=False,
            )(params, sub, sigma, jnp.asarray(round_idx, jnp.int32),
              server, s_idx, s_batch, s_cstate, s_mask)
            enc_sum = constrain_wire(enc_sum)
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
        plan = resolve_cohort(cohort_policy, total, spec.n_coords,
                              spmd_axes)
        if adversary is not None:
            # mid-round dropout fires on the FULL slot mask before anything
            # else, so n_live, loss weighting and state masking all agree
            mask = adversary.drop_mask(jnp.asarray(mask, jnp.float32),
                                       state.round)

        if plan.mode == "stream":
            enc_sum, new_cstate, loss_sum = stream_cohort(
                spec, state.params, batch, mask, state.comp_state, sub,
                sigma, state.round, plan.shard, plan.unroll, plan.devices,
                server=state.comp_server)
        else:
            # per-client keys by global index — identical to the streaming
            # derivation, so the two plans are interchangeable mid-training
            all_keys = znoise.client_keys(sub, 0, total).reshape(
                cfg.client_groups, cfg.n_clients, -1)
            g_indices = jnp.arange(total, dtype=jnp.int32).reshape(
                cfg.client_groups, cfg.n_clients)
            if cfg.client_groups == 1:
                g_batch = jax.tree.map(lambda x: x[0], batch)
                g_cstate = (None if state.comp_state is None
                            else jax.tree.map(lambda x: x[0],
                                              state.comp_state))
                enc_sum, new_cstate_g, loss_sum = math.group_round(
                    spec, state.params, g_batch, all_keys[0], g_cstate,
                    mask[0], sigma, g_indices[0], state.round,
                    state.comp_server)
                new_cstate = (None if new_cstate_g is None
                              else jax.tree.map(lambda x: x[None],
                                                new_cstate_g))
            elif compressor.stacks_group_payloads():
                # NOTE a "flatten small (G, N) rounds into one G*N vmap"
                # gate was tried here (PR 7) and measured AGAINST on XLA
                # CPU: the group lax.scan costs only ~0.1-0.2 ms/step of
                # loop overhead, while widening the vmap regresses the
                # fused packed encode 8-10x (its vmapped tile loop scales
                # superlinearly in the vmapped width — G=8,N=32,d=4096:
                # flattened 420 ms vs group-scan 41 ms; see ROADMAP
                # carry-overs). The scan stays.
                # compressed-domain group scan: the scan OUTPUT is the
                # stacked wire payloads (1 bit/coord for sign families),
                # and the server runs ONE aggregate over the (G*N, ...)
                # stack — no per-group dense f32 partials ever exist.
                def body(loss_acc, xs):
                    g_batch, keys_g, cstate_g, mask_g, idx_g = xs
                    enc, new_cstate_g, loss_sum = math.group_encode(
                        spec, state.params, g_batch, keys_g, cstate_g,
                        mask_g, sigma, idx_g, state.round,
                        state.comp_server)
                    return loss_acc + loss_sum, (enc, new_cstate_g)

                loss_sum, (enc_stack, new_cstate) = jax.lax.scan(
                    body, jnp.zeros(()),
                    (batch, all_keys, state.comp_state, mask, g_indices))
                gn = cfg.client_groups * cfg.n_clients
                enc_all = jax.tree.map(
                    lambda e: e.reshape((gn,) + e.shape[2:]), enc_stack)
                with phase("fed.server.fold"):
                    enc_sum = constrain_wire(
                        compressor.aggregate(enc_all, mask.reshape(-1),
                                             spec.n_coords))
            else:
                # dense fp32 wire: accumulate the decoded group sums in the
                # scan carry (stacking G*N dense payloads would cost G*N*d
                # f32)
                def body(carry, xs):
                    enc_acc, loss_acc = carry
                    g_batch, keys_g, cstate_g, mask_g, idx_g = xs
                    enc_sum, new_cstate_g, loss_sum = math.group_round(
                        spec, state.params, g_batch, keys_g, cstate_g,
                        mask_g, sigma, idx_g, state.round,
                        state.comp_server)
                    with phase("fed.server.fold"):
                        enc_acc = enc_acc + enc_sum
                    return (enc_acc, loss_acc + loss_sum), new_cstate_g

                agg_shape = jax.eval_shape(
                    lambda b, k, c, m: math.group_round(
                        spec, state.params, b, k, c, m, sigma,
                        server=state.comp_server)[0],
                    jax.tree.map(lambda x: x[0], batch), all_keys[0],
                    (None if state.comp_state is None
                     else jax.tree.map(lambda x: x[0], state.comp_state)),
                    mask[0])
                zero_enc = jnp.zeros(agg_shape.shape, agg_shape.dtype)
                (enc_sum, loss_sum), new_cstate = jax.lax.scan(
                    body, (zero_enc, jnp.zeros(())),
                    (batch, all_keys, state.comp_state, mask, g_indices))

        return _finish(state, spec, rng, sigma, enc_sum, new_cstate,
                       loss_sum, mask, plan.shard)

    @phase("fed.server.apply")
    def _finish(state, spec, rng, sigma, enc_sum, new_cstate, loss_sum,
                mask, shard_used):
        n_live = jnp.maximum(jnp.sum(mask), 1.0)
        sig = sigma if dynamic_sigma else None
        spec_kw = ({"spec": spec}
                   if getattr(compressor, "needs_tree_spec", False) else {})
        if hasattr(compressor, "decode_sum"):
            # the codec owns the full sum -> estimate mapping (robust agg=
            # modes decode the int32 vote pair; mean laws divide by n_live)
            g_flat = constrain_wire(
                compressor.decode_sum(enc_sum, n_live, sigma=sig, **spec_kw))
        else:
            # duck-typed legacy compressors: the mean law, spelled out
            g_flat = constrain_wire(
                compressor.decode_mean(enc_sum / n_live, sigma=sig,
                                       **spec_kw))
        # the ONE unflatten: decoded flat estimate -> params-shaped pytree
        g_hat = constrain(spec.unflatten(g_flat))
        # Algorithm 1 line 15: x_t = x_{t-1} - eta * gamma * mean(Delta)
        scaled = jax.tree.map(lambda g: gamma * g, g_hat)
        new_params, new_opt = opt.update(scaled, state.opt_state, state.params)

        # server-scope pipeline state (control variates): fold the decoded
        # mean into the shared variate — exact for mean-law codecs because
        # g_flat is the mean of the per-client local decodes (the same
        # quantity each client folded into its own row this round)
        comp_server = state.comp_server
        if comp_server is not None and hasattr(compressor, "update_server"):
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

    # ---- stream(feed=host): the double-buffered host shard driver -------
    shard_fns = {}

    def _host_shard_fn(spec, shard):
        # one jitted per-shard kernel, cached across rounds; s_idx arrives
        # as a traced uint32 scalar so every shard reuses the same trace
        key = (shard, spec.n_coords)
        if key not in shard_fns:
            def fn(params, sub, sigma, server, round_idx, s_idx, batch_s,
                   cstate_s, mask_s, acc, loss_acc):
                keys_s = znoise.client_keys(sub, s_idx * jnp.uint32(shard),
                                            shard)
                idx_s = (s_idx.astype(jnp.int32) * shard
                         + jnp.arange(shard, dtype=jnp.int32))
                enc, new_cstate_s, loss_s = math.group_encode(
                    spec, params, batch_s, keys_s, cstate_s, mask_s, sigma,
                    idx_s, round_idx, server)
                with phase("fed.server.fold"):
                    acc = compressor.aggregate(enc, mask_s, spec.n_coords,
                                               acc=acc)
                if not isinstance(acc, wire.SignFoldAcc):
                    # structured carries are constrained post-finalize;
                    # launcher wire constraints expect the flat buffer
                    acc = constrain_wire(acc)
                return acc, loss_acc + loss_s, new_cstate_s
            shard_fns[key] = jax.jit(fn)
        return shard_fns[key]

    def host_round_step(state: ServerState, batch, mask):
        """Python-loop round driver for ``stream(feed=host)`` — do NOT wrap
        in jax.jit (it slices host numpy per shard). Bit-identical to the
        device-fed stream: same shard slices, same global-index keys, same
        left-fold accumulator order."""
        spec = wire.tree_spec(state.params)
        plan = resolve_cohort(cohort_policy, total, spec.n_coords,
                              spmd_axes)
        shard = plan.shard
        n_shards = -(-total // shard)
        rng, sub = jax.random.split(state.rng)
        sigma = state.sigma
        stateful = state.comp_state is not None
        if adversary is not None:
            # eager host step: materialize the dropped mask before slicing
            mask = np.asarray(adversary.drop_mask(
                jnp.asarray(mask, jnp.float32), state.round))

        gen = iter_shards(batch, mask, state.comp_state, shard=shard,
                          total=total)
        cur = jax.device_put(next(gen))
        enc_shape = jax.eval_shape(
            lambda b, k, c, m: math.group_encode(
                spec, state.params, b, k, c, m, sigma,
                server=state.comp_server)[0],
            cur[1], znoise.client_keys(sub, 0, shard), cur[2], cur[3])
        acc = (compressor.fold_init(enc_shape)
               if hasattr(compressor, "fold_init") else None)
        if acc is None:
            agg_shape = jax.eval_shape(
                lambda e, m: compressor.aggregate(e, m, spec.n_coords),
                enc_shape, cur[3])
            acc = jnp.zeros(agg_shape.shape, agg_shape.dtype)
        loss_sum = jnp.zeros(())
        fn = _host_shard_fn(spec, shard)
        rows_host, prev_rows = [], None
        for s in range(n_shards):
            # double buffer: upload shard s+1 (async dispatch) before
            # launching shard s's compute ...
            nxt = jax.device_put(next(gen)) if s + 1 < n_shards else None
            acc, loss_sum, rows = fn(state.params, sub, sigma,
                                     state.comp_server, state.round,
                                     *cur, acc, loss_sum)
            # ... and drain shard s-1's finished state rows to host while
            # shard s computes, so only one shard's tensors stay on device
            if stateful and prev_rows is not None:
                rows_host.append(jax.tree.map(np.asarray, prev_rows))
            prev_rows = rows
            cur = nxt
        if hasattr(compressor, "fold_finalize"):
            acc = constrain_wire(compressor.fold_finalize(acc)) \
                if isinstance(acc, wire.SignFoldAcc) else acc
        new_cstate = None
        if stateful:
            rows_host.append(jax.tree.map(np.asarray, prev_rows))
            stacked = jax.tree.map(lambda *rs: np.concatenate(rs, axis=0),
                                   *rows_host)
            new_cstate = jax.tree.map(
                lambda x: x[:total].reshape(
                    (cfg.client_groups, cfg.n_clients) + x.shape[1:]),
                stacked)
        return _finish(state, spec, rng, sigma, acc, new_cstate, loss_sum,
                       jnp.asarray(mask), plan.shard)

    # ---- round_mode=async(...): the deadline-fold driver ----------------
    mode_policy = RoundModePolicy.parse(getattr(ctx, "round_mode", "sync"))
    if mode_policy.mode == "async":
        # the async driver reuses this builder's internals wholesale — the
        # round math, the _finish decode closure, the bound adversary —
        # so its shard pass is the sync host driver's computation exactly
        # (the zero-latency bit-identity pin of tests/test_async_server.py)
        from repro.fed.async_server import build_async_round_step
        return build_async_round_step(
            policy=mode_policy, latency_spec=getattr(ctx, "latency", "zero"),
            compressor=compressor, cfg=cfg, round_math=math, finish=_finish,
            constrain_wire=constrain_wire, cohort_policy=cohort_policy,
            adversary=adversary, total=total)

    return host_round_step if cohort_policy.feed == "host" else round_step


def make_batch_spec(cfg: FedConfig, per_step_batch: dict) -> dict:
    """Shape helper: expand a single-step batch spec to the round layout
    (groups, n_clients, E, ...)."""
    lead = (cfg.client_groups, cfg.n_clients, cfg.local_steps)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype), per_step_batch)
