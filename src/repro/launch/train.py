"""End-to-end federated LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2_0_5b --reduced \
        --rounds 100 --clients 4 --local-steps 2 --compressor zsign \
        --ckpt-dir /tmp/ckpt

Production behavior in one binary: builds the model from the arch registry,
runs z-SignFedAvg rounds on a deterministic token stream, samples partial
participation with straggler over-provisioning, adapts sigma with the Plateau
criterion, checkpoints atomically every ``--save-every`` rounds and
self-resumes from the newest valid checkpoint on restart.

``main(argv)`` is also the in-process entry point (chip_smoke.py calls it):
it returns the final server state and a per-round record.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.common import get_arch
from repro.core import compression, fedavg, spans
from repro.core.plateau import PlateauController
from repro.data.synthetic import TokenStream
from repro.fed.sampling import ParticipationSampler
from repro.models.api import build_model


#: the checkout this module lives in (src/repro/launch/train.py)
REPO_ROOT = Path(__file__).resolve().parents[3]
#: the weights are bundle.init(PRNGKey(PARAMS_SEED)); the server state's
#: rng, from which every round's client keys derive, is PRNGKey(SERVER_SEED)
PARAMS_SEED = 0
SERVER_SEED = 1


def enable_compile_cache(root: Path = REPO_ROOT) -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads the
    variable itself). Otherwise the cache is ``<root>/.jax_cache``: a fixed
    path, because the path is part of the cache key.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class TrainResult(NamedTuple):
    state: fedavg.ServerState
    #: one dict per round: round, loss, ghat_norm, live, sec
    rounds: list
    #: n_params, encode/agg backend and cohort plan as resolved, and for a
    #: jitted round its compile seconds and its count of compiled Pallas
    #: kernel calls (tpu_custom_call)
    info: dict


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--compressor", default="zsign",
                    choices=list(compression.available()))
    ap.add_argument("--pipeline", default=None, metavar="SPEC",
                    help="compression pipeline spec string, overriding "
                         "--compressor and its kwargs — e.g. "
                         "'zsign(z=1,sigma=0.01)', 'ef|topk(frac=0.01)', "
                         "'dp(clip=1.0,eps=2.0)|zsign_packed', or compressed "
                         "SCAFFOLD control variates 'cv|zsign_packed' "
                         "(grammar: docs/API.md)")
    ap.add_argument("--agg-backend", default="auto",
                    choices=list(compression.AGG_BACKENDS),
                    help="sign-family server aggregation backend "
                         "(auto = Pallas kernel on TPU, bit-sliced jnp "
                         "elsewhere)")
    ap.add_argument("--encode-backend", default="auto",
                    choices=list(compression.ENCODE_BACKENDS),
                    help="sign-family client encode backend (auto = in-kernel"
                         " counter noise on TPU, fused jnp elsewhere; "
                         "reference = dense jax.random draw)")
    ap.add_argument("--cohort", default="auto",
                    help="cohort execution policy: 'auto' (stream only when "
                         "the round is large), 'vmap', or 'stream(shard=K|"
                         "auto[,unroll=U][,devices=D|auto][,feed=device|"
                         "host])' — stream runs client shards of K through "
                         "the fused encode under a scan, carrying only the "
                         "reduced wire accumulator; devices=D splits the "
                         "shard sequence over a D-device 'clients' mesh "
                         "with one O(d) psum; feed=host double-buffers "
                         "shards from host memory (grammar: docs/API.md)")
    ap.add_argument("--adversary", default="none", metavar="SPEC",
                    help="wire-level fault-injection policy: 'none', "
                         "'sign_flip(f=4)', 'byte_corrupt(f=2,p=0.1)', "
                         "'collude(f=4,rotate=true)', 'dropout(f=8)', with "
                         "optional every=/start= scheduling — applied to the "
                         "encoded payload stack (or the participation mask) "
                         "under every cohort plan (grammar: "
                         "src/repro/fed/adversary.py, docs/API.md)")
    ap.add_argument("--round-mode", default="sync", metavar="SPEC",
                    help="round execution mode: 'sync' (barrier round) or "
                         "'async(deadline=T[,min_clients=M][,staleness="
                         "none|poly(a)|cutoff(s)])' — deadline-fold round: "
                         "on-time payloads fold now, late ones buffer and "
                         "fold s rounds later at the staleness weight, "
                         "failures get dead-client mask semantics "
                         "(grammar: docs/API.md)")
    ap.add_argument("--latency", default="zero", metavar="SPEC",
                    help="simulated client latency for async rounds: 'zero',"
                         " 'const(t=T)', 'linear(base=B,step=S)', "
                         "'lognormal(median=M,sigma=S)', "
                         "'pareto(xm=X,alpha=A)', each with optional "
                         "fail=P / seed=N (src/repro/fed/async_server.py)")
    ap.add_argument("--debug-wire", action="store_true",
                    help="runtime-verify the 0/1 mask membership contract "
                         "before every popcount reduce (checkify-wrapped "
                         "round step; also via REPRO_DEBUG_WIRE=1)")
    ap.add_argument("--z", type=int, default=1, help="1=Gaussian, 0=uniform")
    ap.add_argument("--sigma", type=float, default=0.01,
                    help="z-sign noise scale / dpgauss noise stddev")
    ap.add_argument("--qsgd-s", type=int, default=1,
                    help="QSGD quantization levels")
    ap.add_argument("--topk-frac", type=float, default=0.01,
                    help="top-k kept fraction")
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--server-lr", type=float, default=0.5)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--over-provision", type=float, default=1.0)
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--plateau", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=20)
    return ap.parse_args(argv)


def build_compressor(args: argparse.Namespace):
    """The compression pipeline the arguments select."""
    if args.pipeline:
        return compression.Pipeline(args.pipeline)
    # legacy per-name kwargs -> the equivalent pipeline (shim-free)
    return {
        "zsign": lambda: compression.ZSignCompressor(
            z=args.z, sigma=args.sigma),
        "zsign_packed": lambda: compression.PackedZSignCompressor(
            z=args.z, sigma=args.sigma),
        "dpgauss": lambda: compression.DPGaussianCompressor(
            sigma=args.sigma),
        "qsgd": lambda: compression.QSGDCompressor(s=args.qsgd_s),
        "topk": lambda: compression.TopKCompressor(frac=args.topk_frac),
        "efsign": compression.EFSignCompressor,
        "stosign": compression.StoSignCompressor,
        "identity": compression.Compressor,
    }[args.compressor]()


def fed_config(args: argparse.Namespace) -> fedavg.FedConfig:
    return fedavg.FedConfig(n_clients=args.clients, client_groups=args.groups,
                            local_steps=args.local_steps,
                            client_lr=args.client_lr, server_lr=args.server_lr)


def make_sampler(args: argparse.Namespace) -> ParticipationSampler:
    """The participation sampler; its t-th ``mask`` is round t's mask."""
    total = args.groups * args.clients
    return ParticipationSampler(
        total_clients=total,
        per_round=max(1, int(total * args.participation)),
        over_provision=args.over_provision, failure_rate=args.failure_rate)


def round_batch(args: argparse.Namespace, bundle, stream: TokenStream,
                t: int) -> dict:
    """Round t's batch: leaves lead with (groups, clients, E, micro)."""
    layout = (args.groups, args.clients, args.local_steps, args.micro_batch)
    per_step = bundle.train_batch_spec(args.micro_batch, args.seq_len)
    tokens = stream.round_batch(t, layout, args.seq_len)
    batch = {"tokens": tokens}
    for name, spec in per_step.items():
        if name == "tokens":
            continue
        key = jax.random.fold_in(jax.random.PRNGKey(7), t)
        batch[name] = jax.random.normal(key, layout + spec.shape[1:],
                                        jnp.float32)
    if "embeds" in per_step or "img_embeds" in per_step:
        s_txt = per_step["tokens"].shape[-1]
        batch["tokens"] = tokens[..., :s_txt]
    return batch


def main(argv=None) -> TrainResult:
    args = parse_args(argv)
    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    bundle = build_model(arch.model)

    comp = build_compressor(args)
    cfg = fed_config(args)
    # ONE typed deployment policy for the round step (core/context.py):
    # CLI backend selectors, the Plateau dynamic-sigma flag, and
    # weights_are_mask=True — the ParticipationSampler below produces exact
    # 0/1 membership masks, so the popcount aggregation specialization is
    # safe. donate_state: params + opt state + residual buffers update in
    # place on device instead of being copied every round.
    ctx_kw = dict(agg_backend=args.agg_backend,
                  encode_backend=args.encode_backend,
                  weights_are_mask=True,
                  dynamic_sigma=args.plateau,
                  cohort=args.cohort,
                  adversary=args.adversary,
                  round_mode=args.round_mode,
                  latency=args.latency)
    if args.debug_wire:  # else keep the REPRO_DEBUG_WIRE env default
        ctx_kw["debug_wire"] = True
    ctx = fedavg.RoundContext(**ctx_kw)
    host_loop = (fedavg.CohortPolicy.parse(args.cohort).feed == "host"
                 or fedavg.RoundModePolicy.parse(args.round_mode).mode
                 == "async")
    if ctx.debug_wire and host_loop:
        raise SystemExit("--debug-wire is not supported on stream(feed=host) "
                         "or async rounds: these host-loop drivers jit "
                         "per-shard kernels internally and cannot "
                         "functionalize the membership check")
    step = fedavg.build_round_step(bundle.loss_fn, comp, cfg, ctx)
    checked = None
    if not host_loop:
        if ctx.debug_wire:
            # debug mode refuses to run unchecked: the membership check is a
            # checkify.check, so the jitted step must be functionalized and
            # its error explicitly thrown each round
            from jax.experimental import checkify
            checked = checkify.checkify(jax.jit(step))
        else:
            step = jax.jit(step,
                           donate_argnums=(0,) if ctx.donate_state else ())
    # else: stream(feed=host) returns a Python-loop driver that device_puts
    # one shard at a time — it must NOT be jitted (and state donation is
    # meaningless for it; the jitted PER-SHARD kernel is cached inside)

    params = bundle.init(jax.random.PRNGKey(PARAMS_SEED))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    state = fedavg.init_server_state(params, cfg, comp,
                                     jax.random.PRNGKey(SERVER_SEED),
                                     sigma0=args.sigma)
    start_round = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr:
        r, restored = mgr.restore_latest(state._asdict())
        if restored is not None:
            state = fedavg.ServerState(**restored)
            start_round = r
            print(f"# resumed from checkpoint at round {r}")

    stream = TokenStream(vocab=arch.model.vocab)
    total = args.groups * args.clients
    sampler = make_sampler(args)
    plateau = (PlateauController(sigma_init=args.sigma,
                                 sigma_bound=args.sigma * 100, kappa=10)
               if args.plateau else None)

    wf = comp.wire_format()
    plan = fedavg.resolve_cohort(args.cohort, total, n_params)
    info = dict(n_params=n_params,
                encode_backend=compression.resolve_backend(
                    "encode", args.encode_backend),
                agg_backend=compression.resolve_backend(
                    "agg", args.agg_backend),
                cohort=plan._asdict())
    print(f"# arch={arch.model.name} params={n_params:,} "
          f"compressor={comp.name} wire={wf.layout}/{wf.dtype} "
          f"({wf.bits_per_coord:g} bits/coord)")
    print(f"# device={jax.devices()[0].device_kind} "
          f"encode={info['encode_backend']} agg={info['agg_backend']} "
          f"cohort={plan.mode}(shard={plan.shard},devices={plan.devices})")
    print("round,loss,ghat_norm,live,Mbits_cum,sigma,sec")

    bits = 0.0
    rounds = []
    for t in range(start_round, args.rounds):
        with spans.host("fed.round", step=t):
            with spans.host("fed.feed"):
                batch = round_batch(args, bundle, stream, t)
                mask = jnp.asarray(sampler.mask((args.groups, args.clients)))
            if checked is None and not host_loop and "compile_s" not in info:
                # compile ahead of the first round, so that its time is
                # set-up and not part of the round's seconds
                log0 = spans.COMPILES.snapshot()
                t0 = time.perf_counter()
                with spans.host("fed.compile"):
                    step = step.lower(state, batch, mask).compile()
                info["compile_s"] = time.perf_counter() - t0
                log1 = spans.COMPILES.snapshot()
                info["compile_log"] = {k: log1[k] - log0[k] for k in log1}
                info["custom_calls"] = step.as_text().count(
                    'custom_call_target="tpu_custom_call"')
                c = info["compile_log"]
                print(f"# compiled the round in {info['compile_s']:.1f}s "
                      f"({info['custom_calls']} tpu_custom_call; "
                      f"{c['compiles']} compiles, {c['cache_hits']} cache "
                      f"hits: trace {c['trace_s']:.1f}s, lower "
                      f"{c['lower_s']:.1f}s, compile or cache load "
                      f"{c['compile_s']:.1f}s)")
            t0 = time.perf_counter()
            if checked is not None:
                err, (state, m) = checked(state, batch, mask)
                err.throw()
            else:
                state, m = step(state, batch, mask)
            jax.block_until_ready((state, m))
            sec = time.perf_counter() - t0
            loss = float(m.loss)
            bits += float(m.uplink_bits)
            if plateau is not None:
                state = state._replace(
                    sigma=jnp.asarray(plateau.update(loss), jnp.float32))
            rounds.append(dict(round=t, loss=loss,
                               ghat_norm=float(m.grad_est_norm),
                               live=int(m.participation), sec=sec))
            print(f"{t},{loss:.4f},{float(m.grad_est_norm):.3f},"
                  f"{int(m.participation)},{bits/1e6:.2f},"
                  f"{float(state.sigma):.4f},{sec:.2f}")
            if mgr and (t + 1) % args.save_every == 0:
                with spans.host("fed.checkpoint"):
                    mgr.save(t + 1, state._asdict())
    if mgr:
        with spans.host("fed.checkpoint"):
            mgr.save(args.rounds, state._asdict())
    print(f"# done: {args.rounds} rounds, {bits/1e6:.1f} Mbit uplink "
          f"({32.0/comp.wire_bits_per_coord:.0f}x less than fp32)")
    return TrainResult(state, rounds, info)


if __name__ == "__main__":
    main()
