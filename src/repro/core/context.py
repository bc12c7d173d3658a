"""RoundContext: the one typed knob-bundle a federated round runs under.

``RoundContext`` makes every deployment policy knob of a round one frozen
value, handed to ``fedavg.build_round_step``:

  * ``agg_backend`` / ``encode_backend`` — backend policy for the server
    sign-reduce and the client fused encode. ``None`` means "keep whatever
    the pipeline stage was built with" (e.g. ``zsign_packed`` pins pallas);
    an explicit string overrides every sign stage in the pipeline.
  * ``weights_are_mask`` — the caller's STATIC guarantee that aggregation
    weights are exact 0/1 participation masks (unlocks the popcount
    sign-reduce specialization; see wire.unpack_sum_mask).
  * ``dynamic_sigma`` — thread the server state's traced sigma (Plateau
    controller) into the codec instead of its static config value.
  * ``donate_state`` — whether drivers donate the server state into the
    jitted round step (in-place params/opt/residual update).
  * ``cohort`` — how the round driver walks the cohort: one vmap over all
    clients, or the streaming shard scan that folds each shard's payloads
    into a running wire accumulator (see :class:`CohortPolicy`).
  * ``debug_wire`` — runtime (checkify) verification of the 0/1-mask
    membership contract on every popcount/vote reduce; defaults from the
    ``REPRO_DEBUG_WIRE`` env var.
  * ``adversary`` — wire-level fault-injection policy (fed/adversary.py
    spec string) applied by the round driver: sign-flip / byte-corruption /
    colluding cohorts / mid-round dropout on a deterministic schedule.

``resolve_backend`` is THE one place an "auto" backend becomes a concrete
one: the Pallas kernels on TPU, the fused jnp paths elsewhere. Everything
that dispatches a kernel (compression.sign_reduce, the sign codec's encode)
calls it, so a deployment can reason about backend selection by reading one
function.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax

#: aggregation backends for the sign-family weighted reduce
AGG_BACKENDS = ("auto", "jnp", "pallas", "dense")

#: client-encode backends for the sign family ("reference" = dense draw)
ENCODE_BACKENDS = ("auto", "jnp", "pallas", "reference")

#: cohort execution modes for the round driver (see CohortPolicy)
COHORT_MODES = ("auto", "vmap", "stream")

#: shard-feeding modes for the streaming plan: "device" keeps the whole
#: cohort's batch/mask/state on device and scans it; "host" drives a
#: double-buffered host loop (fedavg.iter_shards + async jax.device_put of
#: shard t+1 while shard t computes) for cohorts whose mask/key/weight
#: tensors exceed device memory. A feed="host" round step is a Python loop —
#: it must NOT be wrapped in jax.jit.
COHORT_FEEDS = ("device", "host")

#: auto-gate threshold for the streaming cohort executor, in client-coordinate
#: elements (total_clients * n_coords). MEASURED on 1-core XLA CPU, jax
#: 0.4.37 (PR 7, jitted round step, median of 5-7):
#:
#:   * shard lax.scan loop overhead is ~0.1-0.2 ms per scanned shard, NOT
#:     the milliseconds the pre-PR-7 carry-over guessed: a 64-step
#:     stream(shard=4) round over 256 clients at d=1024 runs in 10.0 ms
#:     total, vs 14.4 ms for 16 steps of shard=16 (compute dominates).
#:   * unpacked sign wires: the two plans are within ~5% below the gate
#:     (n=256, d=1024: vmap 14.6 ms vs stream(shard=8) 15.4 ms; ef|zsign
#:     0.64 vs 0.65 ms) — vmap is kept there for its scan-free jaxpr, not
#:     for a large win.
#:   * zsign_packed: historically streaming won at EVERY size because the
#:     default pallas batching rule made the vmapped fused packed encode
#:     superlinear in the vmapped width (d=1024: 1.15 ms at n=16 -> 357 ms
#:     at n=256). PR 9 fixed that lowering (custom_vmap dual rule in
#:     kernels/zsign/ops.py, ~60 us/client flat at n=16..256), so the vmap
#:     plan is usable for packed wires below the gate too.
#:
#: At or above 2**24 elements (~64 MB of dense f32 client gradients) the
#: streaming plan's O(shard * d) working set is required regardless of
#: speed, so the gate stays at the memory bound rather than chasing the
#: wire-format-dependent crossover below it.
STREAM_AUTO_MIN_ELEMS = 1 << 24

#: default clients per shard when a streaming policy does not pin one and
#: shard auto-tuning has nothing to go on (n_coords == 0)
STREAM_DEFAULT_SHARD = 64

#: sentinel for ``stream(shard=auto)`` — fedavg.auto_shard_size picks K from
#: the model coordinate count and STREAM_SHARD_BUDGET_BYTES
STREAM_SHARD_AUTO = -1

#: sentinel for ``stream(devices=auto)`` — resolve_cohort expands it to
#: jax.device_count() at plan-resolution time
COHORT_DEVICES_AUTO = 0

#: per-device memory budget for one in-flight shard of client state. The
#: streaming engine's per-shard working set is ~one dense f32 gradient per
#: client plus the packed wire row (4*d + d/8 bytes per client), so the
#: auto-tuned shard size is budget // (4.125 * d), clamped to
#: [STREAM_SHARD_MIN, STREAM_SHARD_MAX] and rounded down to a multiple of
#: wire.SIGN_REDUCE_CLIENT_BLK to keep the fp32 fold bit-reproducible.
STREAM_SHARD_BUDGET_BYTES = 256 << 20

#: clamp bounds for the auto-tuned stream shard size (clients per shard)
STREAM_SHARD_MIN = 8
STREAM_SHARD_MAX = 512

#: round execution modes: the synchronous barrier (every live client's
#: payload lands before decode) or the async deadline round (see
#: RoundModePolicy)
ROUND_MODES = ("sync", "async")

#: buffered-staleness laws for async rounds: "none" drops late payloads,
#: "poly" down-weights a payload arriving s rounds late by (1+s)^-a,
#: "cutoff" keeps full weight up to s_max rounds late then drops
STALENESS_LAWS = ("none", "poly", "cutoff")

_VALID = {"agg": AGG_BACKENDS, "encode": ENCODE_BACKENDS}


def _split_top(args: str) -> list:
    """Split a spec argument list on TOP-LEVEL commas only, so nested
    parenthesized values — ``staleness=poly(0.5)`` — survive intact."""
    parts, cur, depth = [], [], 0
    for ch in args:
        if ch == "," and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        if depth < 0:
            raise ValueError(f"unbalanced parentheses in {args!r}")
        cur.append(ch)
    if depth != 0:
        raise ValueError(f"unbalanced parentheses in {args!r}")
    parts.append("".join(cur))
    return [p.strip() for p in parts if p.strip()]


def resolve_backend(kind: str, backend: str) -> str:
    """Resolve an ``auto`` backend to a concrete one — the single policy
    point for ``auto|jnp|pallas|reference|dense``.

    ``kind`` is "agg" (server sign-reduce: auto|jnp|pallas|dense) or
    "encode" (client fused encode: auto|jnp|pallas|reference). "auto" picks
    the Pallas kernel on TPU and the fused jnp path everywhere else; any
    other name must be a member of the kind's backend tuple.
    """
    valid = _VALID[kind]
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in valid:
        raise ValueError(f"unknown {kind} backend {backend!r}; "
                         f"expected one of {valid}")
    return backend


@dataclasses.dataclass(frozen=True)
class CohortPolicy:
    """Parsed form of ``RoundContext.cohort`` — how the round driver walks
    the cohort.

      mode="vmap"    each group of ``n_clients`` clients is one shard, one
                     vmap; ``client_groups`` groups run as a shard scan.
      mode="stream"  shard the flat cohort into ``shard``-client slices and
                     ``lax.scan`` them through the fused encode, folding each
                     shard's payload stack into ONE running wire accumulator
                     (compression.Pipeline.aggregate(..., acc=...)). Peak
                     memory O(d) model + O(shard * d/8) wire, any cohort size.
      mode="auto"    stream iff total_clients * n_coords >=
                     STREAM_AUTO_MIN_ELEMS (the small-run regression gate).

    ``shard == 0`` leaves the shard size to the engine (auto-tuned from the
    model coordinate count, see fedavg.auto_shard_size); a bare ``stream``
    spec therefore still auto-gates back to vmap below the threshold, while
    an explicit ``stream(shard=K)`` FORCES streaming at exactly K clients
    per shard (the bit-identity tests rely on this). ``shard=auto``
    (STREAM_SHARD_AUTO) also forces streaming, with the auto-tuned K.
    ``unroll`` is handed to the shard ``lax.scan`` to amortize loop
    overhead.

    ``devices`` adds the cross-device axis: the flat shard sequence is
    partitioned into contiguous per-device slices over a 1-D ``clients``
    mesh with ``shard_map``; each device runs the shard scan on its slice
    and the fp32 wire accumulators meet in ONE ``lax.psum`` (O(d) per device,
    independent of cohort size — the reduce stays in the compressed domain).
    ``devices=1`` (default) is the single-device scan; ``devices=auto``
    (COHORT_DEVICES_AUTO) expands to every local device; any other value
    pins the mesh size. Counter-based client keys make the bits invariant
    to device placement. ``feed`` selects device-resident shards (default)
    or the host-side double-buffered feeder (see COHORT_FEEDS);
    ``feed=host`` is single-device and its round step must not be jitted.
    """
    mode: str = "auto"
    shard: int = 0
    unroll: int = 1
    devices: int = 1
    feed: str = "device"

    def __post_init__(self):
        if self.mode not in COHORT_MODES:
            raise ValueError(f"unknown cohort mode {self.mode!r}; expected "
                             f"one of {COHORT_MODES}")
        if self.shard < STREAM_SHARD_AUTO or self.unroll < 1:
            raise ValueError(f"cohort policy needs shard >= 0 (or 'auto') "
                             f"and unroll >= 1, got shard={self.shard} "
                             f"unroll={self.unroll}")
        if self.devices < COHORT_DEVICES_AUTO:
            raise ValueError(f"cohort policy needs devices >= 1 (or 'auto'),"
                             f" got devices={self.devices}")
        if self.feed not in COHORT_FEEDS:
            raise ValueError(f"unknown cohort feed {self.feed!r}; expected "
                             f"one of {COHORT_FEEDS}")
        if self.mode != "stream":
            for name, val, default in (("shard", self.shard, 0),
                                       ("devices", self.devices, 1),
                                       ("feed", self.feed, "device")):
                if val != default:
                    raise ValueError(f"{name}={val!r} only applies to cohort "
                                     f"mode 'stream', not {self.mode!r}")
        if self.feed == "host" and self.devices != 1:
            raise ValueError("feed='host' is a single-device driver; it "
                             "cannot be combined with devices="
                             f"{self.devices!r}")

    @classmethod
    def parse(cls, spec: "str | CohortPolicy") -> "CohortPolicy":
        """``auto | vmap | stream |
        stream(shard=K|auto[,unroll=U][,devices=D|auto][,feed=device|host])``
        -> policy."""
        if isinstance(spec, cls):
            return spec
        s = spec.strip()
        if "(" not in s:
            return cls(mode=s)
        if not s.endswith(")"):
            raise ValueError(f"malformed cohort spec {spec!r}")
        mode, args = s[:-1].split("(", 1)
        kw = {}
        for part in filter(None, (p.strip() for p in args.split(","))):
            if "=" not in part:
                raise ValueError(f"cohort argument {part!r} in {spec!r} "
                                 f"must be key=value")
            k, v = part.split("=", 1)
            k, v = k.strip(), v.strip()
            if k not in ("shard", "unroll", "devices", "feed"):
                raise ValueError(f"unknown cohort argument {k!r} in "
                                 f"{spec!r}; expected shard=, unroll=, "
                                 f"devices= or feed=")
            if k == "feed":
                kw[k] = v
            elif k == "shard" and v == "auto":
                kw[k] = STREAM_SHARD_AUTO
            elif k == "devices" and v == "auto":
                kw[k] = COHORT_DEVICES_AUTO
            else:
                try:
                    iv = int(v)
                except ValueError:
                    raise ValueError(
                        f"cohort argument {part!r} in {spec!r} must be an "
                        f"integer" + (" or 'auto'"
                                      if k in ("shard", "devices") else "")
                    ) from None
                if iv < 0:
                    raise ValueError(f"cohort argument {part!r} in {spec!r} "
                                     f"must be non-negative")
                kw[k] = iv
        return cls(mode=mode.strip(), **kw)


@dataclasses.dataclass(frozen=True)
class RoundModePolicy:
    """Parsed form of ``RoundContext.round_mode`` — WHEN a round closes.

      mode="sync"    the classic barrier: the round folds every live
                     client's payload, however long the slowest takes.
      mode="async"   deadline-based close (fed/async_server.py): payloads
                     fold into the wire accumulator as they arrive; the
                     round closes at ``deadline`` simulated time units.
                     Clients that miss the deadline are governed by the
                     buffered-staleness law; clients that never report
                     (failures) take the dead-client mask semantics.

    ``deadline`` is required for async and is measured in the latency
    model's time units (one round's compute window). ``min_clients``
    extends the close past the deadline until at least that many live
    payloads have arrived (0 = never extend). ``staleness`` picks the law
    applied to a payload that computes in round r but arrives s > 0 rounds
    later (it folds into round r + s against the server's CURRENT params,
    carrying weight :meth:`stale_weight`):

      none        drop it (pure deadline cutoff)
      poly(a)     fold with weight (1 + s)^-a  (a >= 0)
      cutoff(s)   fold with full weight while s <= s_max, drop beyond

    Invariant (pinned in tests/test_async_server.py): zero latency and a
    deadline covering every client make the async round BIT-IDENTICAL —
    params, residuals, metrics — to the sync streaming round.
    """
    mode: str = "sync"
    deadline: float = 0.0
    min_clients: int = 0
    staleness: str = "none"
    staleness_arg: float = 0.0

    def __post_init__(self):
        if self.mode not in ROUND_MODES:
            raise ValueError(f"unknown round mode {self.mode!r}; expected "
                             f"one of {ROUND_MODES}")
        if self.staleness not in STALENESS_LAWS:
            raise ValueError(f"unknown staleness law {self.staleness!r}; "
                             f"expected one of {STALENESS_LAWS}")
        if self.mode == "sync":
            if (self.deadline, self.min_clients, self.staleness) != \
                    (0.0, 0, "none"):
                raise ValueError("deadline=/min_clients=/staleness= only "
                                 "apply to round mode 'async'")
        else:
            if not self.deadline > 0.0:
                raise ValueError("async round mode needs deadline > 0, got "
                                 f"deadline={self.deadline!r}")
        if self.min_clients < 0 or self.staleness_arg < 0.0:
            raise ValueError("min_clients and the staleness argument must "
                             "be non-negative")

    def stale_weight(self, s: int) -> float:
        """The closed-form buffered-staleness law: fold weight of a payload
        arriving ``s`` rounds after it was computed (s == 0 is on time)."""
        if s <= 0:
            return 1.0
        if self.staleness == "poly":
            return float((1.0 + s) ** (-self.staleness_arg))
        if self.staleness == "cutoff":
            return 1.0 if s <= self.staleness_arg else 0.0
        return 0.0

    @classmethod
    def parse(cls, spec: "str | RoundModePolicy") -> "RoundModePolicy":
        """``sync | async(deadline=T[,min_clients=M]
        [,staleness=none|poly(a)|cutoff(s)])`` -> policy."""
        if isinstance(spec, cls):
            return spec
        s = spec.strip()
        if "(" not in s:
            return cls(mode=s)
        if not s.endswith(")"):
            raise ValueError(f"malformed round_mode spec {spec!r}")
        mode, args = s[:-1].split("(", 1)
        kw = {}
        for part in _split_top(args):
            if "=" not in part:
                raise ValueError(f"round_mode argument {part!r} in {spec!r} "
                                 f"must be key=value")
            k, v = (t.strip() for t in part.split("=", 1))
            if k == "deadline":
                kw["deadline"] = float(v)
            elif k == "min_clients":
                kw["min_clients"] = int(v)
            elif k == "staleness":
                if "(" in v:
                    if not v.endswith(")"):
                        raise ValueError(f"malformed staleness law {v!r} in "
                                         f"{spec!r}")
                    law, arg = v[:-1].split("(", 1)
                    kw["staleness"] = law.strip()
                    kw["staleness_arg"] = float(arg)
                else:
                    kw["staleness"] = v
            else:
                raise ValueError(f"unknown round_mode argument {k!r} in "
                                 f"{spec!r}; expected deadline=, "
                                 f"min_clients= or staleness=")
        return cls(mode=mode.strip(), **kw)


@dataclasses.dataclass(frozen=True)
class RoundContext:
    """Frozen per-deployment policy for one federated round step.

    Constructed once by whoever owns the deployment decision (the train
    CLI, the benchmark, a test) and handed to
    ``fedavg.build_round_step(loss_fn, compressor, cfg, ctx)``; the engine
    applies it to the compression pipeline via ``Pipeline.with_context`` and
    to its own client/aggregation paths. ``None`` backends defer to the
    pipeline stage's own config.
    """
    agg_backend: Optional[str] = None
    encode_backend: Optional[str] = None
    weights_are_mask: bool = False
    dynamic_sigma: bool = False
    donate_state: bool = True
    #: cohort execution policy for the round driver — a CohortPolicy spec
    #: string: "auto" | "vmap" | "stream" | "stream(shard=K|auto[,unroll=U]
    #: [,devices=D|auto][,feed=device|host])"
    cohort: str = "auto"
    #: debug-wire mode: insert a runtime checkify assertion that aggregation
    #: masks honor the 0/1 membership contract before every popcount/vote
    #: reduce (wire.check_mask_membership). Defaults from the
    #: REPRO_DEBUG_WIRE env var ("1"/"true" enables). A debug-wire round
    #: step must run eagerly or be functionalized:
    #: ``err, out = checkify.checkify(jax.jit(step))(...); err.throw()``.
    debug_wire: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "REPRO_DEBUG_WIRE", "").lower() in ("1", "true", "yes"))
    #: wire-level fault-injection policy for the round driver — an
    #: fed/adversary.py spec string: "none" | "sign_flip(f=4)" |
    #: "byte_corrupt(f=2,p=0.1)" | "collude(f=4)" | "dropout(f=8)"
    #: (+ schedule args every=/start=/rotate=/seed=)
    adversary: str = "none"
    #: round execution mode — a RoundModePolicy spec string: "sync" |
    #: "async(deadline=T[,min_clients=M][,staleness=none|poly(a)|
    #: cutoff(s)])". Async rounds are driven by fed/async_server.py: a
    #: host-side event loop that folds payloads into the wire accumulator
    #: as they arrive and closes at the deadline. An async round step is a
    #: Python loop — it must NOT be wrapped in jax.jit.
    round_mode: str = "sync"
    #: simulated client latency/failure model for async rounds — an
    #: fed/async_server.py spec string: "zero" | "const(t=T)" |
    #: "linear(base=B,step=S)" | "lognormal(median=M,sigma=S)" |
    #: "pareto(xm=X,alpha=A)" (+ fail=P failure rate, seed=N). Only
    #: meaningful with round_mode="async".
    latency: str = "zero"

    def __post_init__(self):
        # fail at construction, not at trace time inside the round step —
        # membership is owned by resolve_backend / CohortPolicy, reused here
        for kind, backend in (("agg", self.agg_backend),
                              ("encode", self.encode_backend)):
            if backend is not None:
                resolve_backend(kind, backend)
        CohortPolicy.parse(self.cohort)
        mode = RoundModePolicy.parse(self.round_mode)
        if self.latency != "zero":
            if mode.mode != "async":
                raise ValueError("latency= is a simulation knob of async "
                                 "rounds; set round_mode='async(...)' or "
                                 "leave latency='zero'")
            # validate eagerly; imported lazily to keep core free of a
            # module-load dependency on the fed layer
            from repro.fed.async_server import parse_latency
            parse_latency(self.latency)
        if self.adversary != "none":
            # validate eagerly; imported lazily to keep core free of a
            # module-load dependency on the fed layer
            from repro.fed.adversary import parse_adversary
            parse_adversary(self.adversary)
