"""Async, deadline-based federated round driver (straggler tolerance).

The synchronous round is a barrier: decode waits for the slowest live
client. This module makes "when does the round close" a first-class policy
(:class:`repro.core.context.RoundModePolicy`, spec
``round_mode="async(deadline=T[,min_clients=M][,staleness=...])"``): a
host-side event loop walks the cohort in shard order, folding each
arriving payload into the wire accumulator immediately — the same
``Pipeline.aggregate(..., acc=...)`` fold hooks the streaming engine
uses — and closes the round at a participation deadline.

Simulated time. Client wall-clock latency comes from a deterministic
:class:`LatencyModel` (the ``RoundContext.latency`` spec): per
(seed, round) the model draws one latency per client; failures draw +inf.
One round's compute window is the time unit, so a client with latency 2.7
under deadline 1.0 reports during round r+2. The partition of a round's
cohort:

  * ON TIME  (latency <= effective deadline): payload folds into THIS
    round at its mask weight — indistinguishable from the sync round.
  * LATE     (finite latency past the deadline): the client still
    computes — against the params of the round it was scheduled in — and
    its payload is buffered host-side, arriving in round r + s
    (s = ceil(latency / deadline) - 1, at least 1) where it folds at the
    buffered-staleness weight ``RoundModePolicy.stale_weight(s)``. A zero
    stale weight means the client is dropped instead (it never computes).
  * DEAD     (mask 0, adversary dropout, or a latency-model failure):
    ordinary dead-client mask semantics — no compute, residuals frozen.

``min_clients=M`` extends the close past the deadline until the M fastest
live payloads have arrived (the classic buffered-async guard against
near-empty rounds).

THE invariant (pinned in tests/test_async_server.py): with zero simulated
latency and a deadline covering every client, the async round is
BIT-IDENTICAL — params, residuals, metrics — to the sync
``stream(feed=host)`` round (itself pinned bit-identical to the device
stream and vmap plans). This falls out of construction, not tolerance
windows: the async driver runs the same per-shard computation as the sync
host driver (same global-index client keys, same shard slices, same
partition-invariant ``wire.SignFoldAcc`` fold), plus an empty pending
buffer.

Adversaries compose: ``RoundContext.adversary`` dropout hits the mask
before the latency partition, and payload corruption is injected inside
``group_encode`` by global client index + round — identical bytes under
the sync and async drivers.

An async round step is a Python loop (host-side event queue + numpy
buffers). It must NOT be wrapped in jax.jit, and its late-payload queue
lives in the step closure — drive ONE training run per built step (build
another step for a second run; reusing one step across interleaved runs
would cross their queues). Entry point: ``fedavg.build_round_step``
dispatches here when the context says ``round_mode="async(...)"``; this
module is never imported otherwise.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import wire
from repro.core.context import RoundModePolicy

#: latency model kinds (RoundContext.latency spec heads)
LATENCY_KINDS = ("zero", "const", "linear", "lognormal", "pareto")


@dataclasses.dataclass(frozen=True)
class LatencyModel:
    """Deterministic simulated client latency + failure draw.

    One draw per (seed, round, client); the time unit is one round's
    compute window (the async deadline is expressed in the same unit).

      zero                          every client reports instantly
      const(t=T)                    every client takes T
      linear(base=B,step=S)         client i takes B + S*i (closed-form —
                                    the exactness-test workhorse)
      lognormal(median=M,sigma=S)   heavy tail: M * exp(S * N(0,1))
      pareto(xm=X,alpha=A)          heavier tail: classic Pareto(xm, alpha)

    ``fail=P`` gives every client an independent per-round probability of
    never reporting (latency +inf -> dead-client semantics). All draws
    come from one numpy RandomState seeded by (seed, round), so the same
    spec replays the same stragglers on any machine.
    """
    kind: str = "zero"
    t: float = 0.0
    base: float = 0.0
    step: float = 0.0
    median: float = 1.0
    sigma: float = 1.0
    xm: float = 1.0
    alpha: float = 1.5
    fail: float = 0.0
    seed: int = 0

    def __post_init__(self):
        if self.kind not in LATENCY_KINDS:
            raise ValueError(f"unknown latency kind {self.kind!r}; expected "
                             f"one of {LATENCY_KINDS}")
        if not 0.0 <= self.fail < 1.0:
            raise ValueError(f"latency fail= must be in [0, 1), got "
                             f"{self.fail!r}")
        if self.kind == "pareto" and self.alpha <= 0.0:
            raise ValueError("pareto latency needs alpha > 0")

    def sample(self, round_idx: int, n: int) -> np.ndarray:
        """(n,) float64 latencies for this round; failed clients get +inf."""
        rs = np.random.RandomState(
            (self.seed * 1000003 + int(round_idx) * 7919 + 17) % (1 << 32))
        if self.kind == "zero":
            lat = np.zeros(n)
        elif self.kind == "const":
            lat = np.full(n, float(self.t))
        elif self.kind == "linear":
            lat = self.base + self.step * np.arange(n, dtype=np.float64)
        elif self.kind == "lognormal":
            lat = self.median * np.exp(self.sigma * rs.randn(n))
        else:  # pareto
            lat = self.xm * (1.0 + rs.pareto(self.alpha, n))
        if self.fail > 0.0:
            lat = np.where(rs.rand(n) < self.fail, np.inf, lat)
        return lat


def parse_latency(spec) -> LatencyModel:
    """``zero | const(t=T) | linear(base=B,step=S) |
    lognormal(median=M,sigma=S) | pareto(xm=X,alpha=A)`` with optional
    ``fail=P`` / ``seed=N`` arguments -> :class:`LatencyModel`."""
    if isinstance(spec, LatencyModel):
        return spec
    s = spec.strip()
    if "(" not in s:
        return LatencyModel(kind=s)
    if not s.endswith(")"):
        raise ValueError(f"malformed latency spec {spec!r}")
    kind, args = s[:-1].split("(", 1)
    kw = {}
    for part in filter(None, (p.strip() for p in args.split(","))):
        if "=" not in part:
            raise ValueError(f"latency argument {part!r} in {spec!r} must "
                             f"be key=value")
        k, v = (t.strip() for t in part.split("=", 1))
        if k == "seed":
            kw[k] = int(v)
        elif k in ("t", "base", "step", "median", "sigma", "xm", "alpha",
                   "fail"):
            kw[k] = float(v)
        else:
            raise ValueError(f"unknown latency argument {k!r} in {spec!r}")
    return LatencyModel(kind=kind.strip(), **kw)


def staleness_rounds(lat: np.ndarray, deadline: float) -> np.ndarray:
    """Closed-form arrival lag: a payload with latency ``lat`` computed in
    round r arrives in round r + s, s = ceil(lat / deadline) - 1, clamped
    to >= 1 — anything past the deadline waits for at least the NEXT fold
    opportunity. Vectorized; +inf stays +inf."""
    with np.errstate(invalid="ignore"):
        s = np.ceil(np.asarray(lat, np.float64) / float(deadline)) - 1.0
    return np.maximum(s, 1.0)


def partition_round(policy: RoundModePolicy, lat: np.ndarray,
                    live: np.ndarray):
    """Split one round's cohort by the deadline law.

    Returns ``(on_time, stale_s, stale_w, close_time)``: boolean on-time
    selector, per-client integer arrival lag (0 where not late-folding),
    per-client stale fold weight (0 where dropped), and the simulated
    round close time — the last on-time arrival, or the effective deadline
    when someone is late (``min_clients`` may have extended it). All
    numpy, all deterministic.
    """
    lat = np.asarray(lat, np.float64)
    live = np.asarray(live, bool)
    finite = live & np.isfinite(lat)
    eff_t = float(policy.deadline)
    if policy.min_clients > 0 and np.any(finite):
        have = int(np.sum(finite & (lat <= eff_t)))
        if have < policy.min_clients:
            cand = np.sort(lat[finite])
            kth = cand[min(policy.min_clients, cand.size) - 1]
            eff_t = max(eff_t, float(kth))
    on_time = finite & (lat <= eff_t)
    late = finite & ~on_time
    s = np.zeros(lat.shape, np.int64)
    w = np.zeros(lat.shape, np.float64)
    if np.any(late):
        s_late = staleness_rounds(lat[late], policy.deadline).astype(np.int64)
        w_late = np.array([policy.stale_weight(int(si)) for si in s_late])
        s[late] = np.where(w_late > 0.0, s_late, 0)
        w[late] = w_late
    if np.any(late) or not np.any(on_time):
        close = eff_t
    else:
        close = float(np.max(lat[on_time]))
    return on_time, s, w, close


def simulate_close_times(policy: RoundModePolicy, model: LatencyModel,
                         rounds: int, total: int) -> np.ndarray:
    """(rounds, 2) simulated round close times: column 0 the async close
    (:func:`partition_round`), column 1 the sync barrier — the slowest
    FINITE live latency (a sync round with a failed client never closes,
    so failures are excluded from the barrier). Feeds the benchmark's
    p50/p90 round-latency rows."""
    out = np.empty((rounds, 2))
    live = np.ones(total, bool)
    for r in range(rounds):
        lat = model.sample(r, total)
        out[r, 0] = partition_round(policy, lat, live)[3]
        finite = np.isfinite(lat)
        out[r, 1] = float(np.max(lat[finite])) if np.any(finite) else 0.0
    return out


def build_async_round_step(*, policy: RoundModePolicy, latency_spec,
                           compressor, host_walk, finish,
                           cohort_policy, adversary, total: int):
    """Assemble the async round driver. Called ONLY from
    ``fedavg.build_round_step`` (which owns context resolution, the shard
    executor, and the ``_finish`` decode closure); every argument after
    ``policy``/``latency_spec`` is one of that builder's internals, handed
    over so the async driver runs the sync host walk (``host_walk``) with
    its own fold weights.

    Returns ``async_round_step(state, batch, mask) -> (state, metrics)`` —
    a host Python loop (do not jit)."""
    from repro.core import fedavg  # deferred: breaks the core<->fed cycle

    latency = parse_latency(latency_spec)
    if policy.staleness == "poly" and getattr(compressor.codec,
                                              "weights_are_mask", False):
        raise ValueError(
            "staleness=poly(...) folds FRACTIONAL stale weights, which "
            "breaks the static weights_are_mask 0/1 contract (and the "
            "vote/popcount aggregation laws built on it). Use "
            "staleness=cutoff(s) with this pipeline, or drop "
            "weights_are_mask.")
    #: host-side event queue: arrival round -> list of
    #: (compute_round, client_id, fold_weight, payload_row); rows are
    #: numpy trees, replayed in (compute_round, client_id) order
    pending = {}

    def async_round_step(state, batch, mask):
        """Async round driver: shard walk + deadline fold + stale-payload
        queue. Python loop — do NOT wrap in jax.jit."""
        spec = wire.tree_spec(state.params)
        plan = fedavg.resolve_cohort(cohort_policy, total, spec.n_coords)
        shard = plan.shard if plan.mode == "stream" else total
        rng, sub = jax.random.split(state.rng)
        r = int(state.round)

        mask_np = np.asarray(mask, np.float32)
        if adversary is not None:
            mask_np = np.asarray(adversary.drop_mask(
                jnp.asarray(mask_np, jnp.float32), state.round))
        flat_mask = mask_np.reshape(total)

        lat = latency.sample(r, total)
        on_time, stale_s, stale_w, _ = partition_round(
            policy, lat, flat_mask > 0.0)
        # the compute mask gates the client step + residual update (late
        # clients DO compute, against this round's params); the fold
        # weight keeps only the on-time payloads in this round's
        # accumulator. Zero latency makes the two vectors equal — and the
        # shard pass below byte-identical to the sync host driver's.
        computes = on_time | (stale_w > 0.0)
        compute_mask = (flat_mask * computes).astype(np.float32)
        fold_w = (flat_mask * on_time).astype(np.float32)
        late_ids = np.nonzero((stale_w > 0.0) & ~on_time
                              & (flat_mask > 0.0))[0]
        fold_w_pad = np.zeros(-(-total // shard) * shard, np.float32)
        fold_w_pad[:total] = fold_w

        def queue_late(lo, enc):
            # queue this shard's late payload rows for their arrival round
            # (each client id < total owns exactly one non-pad slot)
            for cid in late_ids[(late_ids >= lo) & (late_ids < lo + shard)]:
                row = jax.tree.map(lambda x: np.asarray(x[int(cid) - lo]),
                                   enc)
                pending.setdefault(r + int(stale_s[cid]), []).append(
                    (r, int(cid), float(flat_mask[cid] * stale_w[cid]),
                     row))

        acc, loss_sum, new_cstate = host_walk(
            state, spec, sub, batch, compute_mask.reshape(mask_np.shape),
            shard, fold_w=fold_w_pad, on_payload=queue_late)

        # fold the stale payloads ARRIVING this round, in deterministic
        # (compute_round, client_id) order, each at its staleness weight
        stale_weight_sum = 0.0
        for _, _, w, row in sorted(pending.pop(r, []),
                                   key=lambda e: (e[0], e[1])):
            stacked = jax.tree.map(lambda x: jnp.asarray(x)[None], row)
            acc = compressor.aggregate(stacked,
                                       jnp.asarray([w], jnp.float32),
                                       spec.n_coords, acc=acc)
            stale_weight_sum += w

        # the effective participation of the round: on-time mask weights
        # plus the stale weights folded in — _finish divides the decoded
        # mean by its sum, exactly the total weight the accumulator
        # carries. (The stale total rides on slot 0; _finish only reduces
        # the vector.) The loss metric instead covers every client that
        # COMPUTED this round, late ones included — it measures this
        # round's params, not this round's fold.
        eff_w = fold_w.copy()
        eff_w[0] += np.float32(stale_weight_sum)
        eff_mask = jnp.asarray(eff_w.reshape(mask_np.shape))
        return finish(state, spec, rng, state.sigma,
                      compressor.fold_finalize(acc), new_cstate, loss_sum,
                      eff_mask, plan.shard)

    return async_round_step
