"""Run the federated round on a TPU through the training entry point, and
check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a four-chip host: the cross-chip cohort

One chip: qwen2-0.5B at its published widths (24 layers, d_model 896,
vocab 151936, bf16 weights from a seed) trains 3 rounds of z-SignFedAvg
through ``repro.launch.train.main`` with the default ``auto`` backends, which
resolve to the Pallas encode and sign-reduce on the TPU: 4 clients, 2 local
steps of 1 x 512 tokens each, the clients streamed one at a time
(``stream(shard=1)``) so that the round fits one chip's 16 GB. Then round 0's
pseudo-gradients are recomputed and encoded by the Pallas and the jnp
backends under the round's client keys; the payload bytes, and their reduced
sums under the round's mask, must be identical.

Four chips (``--four-chips``, and nothing else): one round with the 4
clients spread over a 4-device ``clients`` mesh (``stream(shard=1,
devices=4)``) and one on a single device, from the same state; the new
weights must be bit-identical.

Everything runs in this one process, which holds the chips. The script exits
non-zero, without its last line, when the default device is not a TPU, when
any phase fails, when the backends do not resolve to ``pallas``, or when the
compiled round holds no Pallas kernel (``tpu_custom_call``). Its last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SPEC = "zsign_packed(z=1,sigma=0.01)"
TRAIN_ARGV = ["--arch", "qwen2_0_5b", "--pipeline", SPEC, "--clients", "4",
              "--local-steps", "2", "--micro-batch", "1", "--seq-len", "512"]
ROUNDS = 3
#: clients per streamed shard: the largest whose compiled round fits 16 GB
SHARD = 1
#: tiles per scan step of the jnp encode: its single pass does not fit at
#: this width on the TPU
JNP_CHUNK_TILES = 64
#: payload bytes per jnp reduce call (its (bytes, 8) f32 output is padded to
#: 128 lanes on the TPU, so the whole stack at once would not fit)
REDUCE_CHUNK_BYTES = 1 << 21


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def gib(n: int) -> str:
    return f"{n / 2**30:.2f} GiB"


def train_argv(cohort: str, rounds: int) -> list:
    return TRAIN_ARGV + ["--rounds", str(rounds), "--cohort", cohort]


def check_round(res, devices: int) -> None:
    info = res.info
    print(f"# params={info['n_params']:,} encode={info['encode_backend']} "
          f"agg={info['agg_backend']} cohort={info['cohort']}")
    print(f"# round compile {info['compile_s']:.2f} s, "
          f"{info['custom_calls']} tpu_custom_call")
    check(info["encode_backend"] == "pallas" and info["agg_backend"] == "pallas",
          f"backends resolved to {info['encode_backend']}/"
          f"{info['agg_backend']}, not pallas/pallas")
    check(info["cohort"]["devices"] == devices,
          f"cohort plan {info['cohort']} is not on {devices} device(s)")
    check(info["custom_calls"] > 0, "the compiled round holds no Pallas kernel")
    for r in res.rounds:
        print(f"# round {r['round']}: loss {r['loss']!r} "
              f"{r['sec']:.4f} s (block_until_ready)")
        check(math.isfinite(r["loss"]), f"round {r['round']} loss {r['loss']}")


def backends_agree(jax, train) -> None:
    """Round 0's pseudo-gradients, encoded and reduced by both backends."""
    import jax.numpy as jnp

    from repro.configs.common import get_arch
    from repro.core import compression, fedavg, wire
    from repro.core import noise as znoise
    from repro.data.synthetic import TokenStream
    from repro.models.api import build_model

    args = train.parse_args(train_argv(f"stream(shard={SHARD})", 1))
    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    bundle = build_model(arch.model)
    cfg = train.fed_config(args)
    params0 = bundle.init(jax.random.PRNGKey(train.PARAMS_SEED))
    batch = train.round_batch(args, bundle, TokenStream(vocab=arch.model.vocab),
                              0)
    mask = jnp.asarray(train.make_sampler(args).mask(
        (args.groups, args.clients))).reshape(-1)
    # the round step's derivation: split the server rng, fold in the index
    _, sub = jax.random.split(jax.random.PRNGKey(train.SERVER_SEED))
    keys = znoise.client_keys(sub, 0, args.groups * args.clients)

    def codec(backend, spec=SPEC):
        return compression.Pipeline(spec).with_context(fedavg.RoundContext(
            encode_backend=backend, agg_backend=backend,
            weights_are_mask=True))

    pallas = codec("pallas")
    jnp_codec = codec("jnp", SPEC.replace(
        ")", f",encode_chunk_tiles={JNP_CHUNK_TILES})"))
    pseudo = jax.jit(lambda p, b: fedavg.client_pseudo_gradient(
        bundle.loss_fn, cfg, wire.tree_spec(p), p, b))
    enc_p = jax.jit(lambda k, f: pallas.encode(k, f, None)[0])
    enc_j = jax.jit(lambda k, f: jnp_codec.encode(k, f, None)[0])
    flat_batch = jax.tree.map(
        lambda x: x.reshape((-1,) + x.shape[2:]), batch)

    payloads = []
    for c in range(keys.shape[0]):
        flat, loss = pseudo(params0, jax.tree.map(lambda x: x[c], flat_batch))
        a, b = enc_p(keys[c], flat), enc_j(keys[c], flat)
        diff = int(jnp.sum(a != b))
        print(f"# client {c}: loss {float(loss)!r}, {a.size} payload bytes, "
              f"{diff} differ between pallas and jnp")
        check(bool(jnp.isfinite(loss)), f"client {c} loss {float(loss)}")
        check(a.shape == b.shape and diff == 0,
              f"client {c}: pallas and jnp payloads differ in {diff} bytes")
        payloads.append(a)
        del flat
    stacked = jnp.stack(payloads)
    del payloads

    n_bytes = stacked.shape[1]
    full = jax.jit(lambda p, m: pallas.aggregate(p, m, 8 * n_bytes))(
        stacked, mask)
    size = min(REDUCE_CHUNK_BYTES, n_bytes)

    @jax.jit
    def mismatches(stacked, mask, full, start):
        part = jax.lax.dynamic_slice_in_dim(stacked, start, size, axis=1)
        got = jnp_codec.aggregate(part, mask, 8 * size)
        ref = jax.lax.dynamic_slice_in_dim(full, 8 * start, 8 * size)
        return jnp.sum(got != ref)

    # the last chunk is moved back to end at n_bytes, overlapping its
    # neighbour, so every call has the one compiled shape
    starts = sorted({min(s, n_bytes - size) for s in range(0, n_bytes, size)})
    diff = sum(int(mismatches(stacked, mask, full, jnp.int32(s)))
               for s in starts)
    print(f"# reduce of {stacked.shape[0]} payloads under mask "
          f"{mask.tolist()}: {full.size} sums, {diff} differ between pallas "
          f"and jnp")
    check(diff == 0, f"pallas and jnp reduce sums differ in {diff} coords")


def one_chip(jax, train) -> None:
    t0 = time.perf_counter()
    res = train.main(train_argv(f"stream(shard={SHARD})", ROUNDS))
    print(f"# trained {ROUNDS} rounds in {time.perf_counter() - t0:.1f} s "
          f"(set-up and compile included)")
    check_round(res, devices=1)
    del res
    stats = jax.devices()[0].memory_stats() or {}
    print(f"# peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"({gib(stats.get('peak_bytes_in_use', 0))}) of "
          f"{stats.get('bytes_limit')}")
    t0 = time.perf_counter()
    backends_agree(jax, train)
    print(f"# backend comparison took {time.perf_counter() - t0:.1f} s")


def four_chips(jax, train) -> None:
    import numpy as np

    check(jax.device_count() >= 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")
    new_params = {}
    for devices in (4, 1):
        res = train.main(train_argv(
            f"stream(shard=1,devices={devices})", 1))
        check_round(res, devices=devices)
        if devices == 4:
            for i, d in enumerate(jax.devices()[:4]):
                s = d.memory_stats() or {}
                print(f"# device {i} ({d}): peak_bytes_in_use "
                      f"{s.get('peak_bytes_in_use')} bytes_in_use "
                      f"{s.get('bytes_in_use')}")
        new_params[devices] = [np.asarray(x) for x in
                               jax.tree_util.tree_leaves(res.state.params)]
        del res
    differ = sum(int(np.sum(a.view(np.uint16) != b.view(np.uint16)))
                 for a, b in zip(new_params[4], new_params[1]))
    n = sum(a.size for a in new_params[1])
    print(f"# devices=4 vs devices=1: {differ} of {n} new weights differ")
    check(differ == 0, f"devices=4 and devices=1 rounds differ in {differ} "
                       "weights")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cohort split over a 4-device mesh, "
                         "against the same round on one device")
    opts = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch import train

    train.enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the default device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    print(f"# device {dev.device_kind}, {jax.device_count()} visible")
    try:
        (four_chips if opts.four_chips else one_chip)(jax, train)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
