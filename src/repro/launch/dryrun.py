import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh with 512 placeholder host devices, and extract the roofline
terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_0_5b \
        --shape train_4k [--multi-pod] [--out results.json]

For each cell this prints/records:
  * compiled memory_analysis (bytes per device — proves it fits),
  * cost_analysis FLOPs / bytes accessed,
  * collective bytes summed from the optimized HLO (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute),
  * the three roofline terms vs TPU v5e (197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI).
"""

import argparse
import dataclasses
import json
import re
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.common import SHAPES, get_arch, list_archs
from repro.core import compression, fedavg
from repro.launch import sharding as SH
from repro.launch.hints import sharding_hints
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s/link (per-chip aggregate approximation)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def _dryrun_model(arch, shape):
    """Dry-run model cfg hook. Attention is flash-style KV-chunked
    (layers._flash_kv_attention), which is sharding-transparent — no
    override needed; kept as the per-cell tuning point for §Perf."""
    del shape
    return arch.model


def build_train_cell(arch, shape, mesh, agg_backend="auto",
                     encode_backend="auto", cohort="auto",
                     adversary="none", pipeline=None):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs).

    ``pipeline`` overrides the arch's default zsign codec with a full
    pipeline spec string (e.g. ``cv|zsign_packed``) — proves stateful
    pipelines lower/compile on the production mesh with their client-scope
    slots cohort-sharded and server-scope slots replicated."""
    arch = __import__("dataclasses").replace(arch, model=_dryrun_model(arch, shape))
    bundle = build_model(arch.model)
    plan = SH.make_plan(arch, shape, mesh)
    comp = compression.Pipeline(
        pipeline if pipeline else
        f"zsign(z={arch.zsign_z},sigma={arch.zsign_sigma})")
    fcfg = fedavg.FedConfig(n_clients=plan.n_clients,
                            client_groups=plan.client_groups,
                            local_steps=plan.local_steps,
                            client_lr=arch.client_lr,
                            server_lr=arch.server_lr)
    params_shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pspecs = SH.param_specs(params_shapes, mesh, plan,
                            moe_experts=arch.model.moe_experts)
    psh = SH.to_shardings(pspecs, mesh)

    def param_constraint(tree):
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, psh)

    rep = SH.replicated(mesh)

    ctx = SH.round_context(plan, agg_backend=agg_backend,
                           encode_backend=encode_backend, cohort=cohort,
                           adversary=adversary)
    step = fedavg.build_round_step(
        bundle.loss_fn, comp, fcfg, ctx,
        spmd_axes=(plan.client_axes if plan.client_axes else None),
        param_constraint=param_constraint,
        wire_constraint=lambda f: jax.lax.with_sharding_constraint(f, rep))

    state_shapes = jax.eval_shape(
        lambda p: fedavg.init_server_state(p, fcfg, comp,
                                           jax.random.PRNGKey(0)),
        params_shapes)
    comp_state_sh = (None if state_shapes.comp_state is None else
                     SH.to_shardings(SH.wire_state_specs(
                         state_shapes.comp_state, plan), mesh))
    comp_server_sh = (None if state_shapes.comp_server is None else
                      SH.to_shardings(SH.server_state_specs(
                          state_shapes.comp_server, plan), mesh))
    state_sh = fedavg.ServerState(
        params=psh, opt_state=(), comp_state=comp_state_sh, rng=rep,
        round=rep, sigma=rep, comp_server=comp_server_sh)

    per_step = bundle.train_batch_spec(plan.micro, shape.seq_len)
    batch_shapes = fedavg.make_batch_spec(fcfg, per_step)
    bspecs = SH.batch_specs(batch_shapes, plan)
    bsh = SH.to_shardings(bspecs, mesh)
    mask_shape = jax.ShapeDtypeStruct(
        (plan.client_groups, plan.n_clients), jnp.float32)
    mask_sh = NamedSharding(mesh, P(None, SH._axes_entry(plan.client_axes)))

    # donate the server state: in-place params/opt/residual update shows up
    # in the compiled memory analysis as aliased buffers, not copies
    fn = jax.jit(step, in_shardings=(state_sh, bsh, mask_sh),
                 out_shardings=(state_sh, rep), donate_argnums=0)
    return fn, (state_shapes, batch_shapes, mask_shape), plan


def build_prefill_cell(arch, shape, mesh):
    """Prefill: forward to final hidden + last-token logits (serving)."""
    arch = __import__("dataclasses").replace(arch, model=_dryrun_model(arch, shape))
    bundle = build_model(arch.model)
    plan = SH.make_plan(arch, shape, mesh)
    cfg = arch.model
    batch = shape.global_batch
    all_batch_axes = tuple(list(plan.client_axes) + list(plan.micro_axes))

    if cfg.family in ("dense", "moe", "vlm", "mla_moe"):
        from repro.models import mla_moe, transformer
        T = mla_moe if cfg.family == "mla_moe" else transformer

        def prefill(params, tokens):
            x, _ = T.forward_hidden(params, tokens, cfg)
            return (x[:, -1:] @ T.lm_head(params, cfg)).astype(jnp.float32)
        arg_shapes = (jax.ShapeDtypeStruct((batch, shape.seq_len), jnp.int32),)
        aspec = (P(SH._axes_entry(all_batch_axes), SH._axes_entry(plan.seq_axes)),)
    elif cfg.family == "hybrid":
        from repro.models import hybrid as Hy
        def prefill(params, tokens):
            x, _ = Hy.forward_hidden(params, tokens, cfg)
            return (x[:, -1:] @ Hy._head(params, cfg)).astype(jnp.float32)
        arg_shapes = (jax.ShapeDtypeStruct((batch, shape.seq_len), jnp.int32),)
        aspec = (P(SH._axes_entry(all_batch_axes), SH._axes_entry(plan.seq_axes)),)
    elif cfg.family == "xlstm":
        from repro.models import xlstm as X
        def prefill(params, tokens):
            x = X.forward_hidden(params, tokens, cfg)
            return (x[:, -1:] @ X._head(params, cfg)).astype(jnp.float32)
        arg_shapes = (jax.ShapeDtypeStruct((batch, shape.seq_len), jnp.int32),)
        aspec = (P(SH._axes_entry(all_batch_axes), SH._axes_entry(plan.seq_axes)),)
    else:  # encdec
        from repro.models import encdec as E
        s_src = shape.seq_len // 2
        def prefill(params, embeds):
            mem = E.encode(params, embeds, cfg)
            return mem[:, -1:]
        arg_shapes = (jax.ShapeDtypeStruct((batch, s_src, cfg.d_model),
                                           jnp.float32),)
        aspec = (P(SH._axes_entry(all_batch_axes), SH._axes_entry(plan.seq_axes),
                   None),)

    params_shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pspecs = SH.param_specs(params_shapes, mesh, plan,
                            moe_experts=cfg.moe_experts)
    psh = SH.to_shardings(pspecs, mesh)
    ash = tuple(NamedSharding(mesh, s) for s in aspec)
    fn = jax.jit(prefill, in_shardings=(psh,) + ash)
    return fn, (params_shapes,) + arg_shapes, plan


def build_decode_cell(arch, shape, mesh):
    """One-token decode with a KV/state cache of shape.seq_len."""
    bundle = build_model(arch.model)
    plan = SH.make_plan(arch, shape, mesh)
    cfg = arch.model
    batch = shape.global_batch

    params_shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    pspecs = SH.param_specs(params_shapes, mesh, plan,
                            moe_experts=cfg.moe_experts)
    psh = SH.to_shardings(pspecs, mesh)
    rep = SH.replicated(mesh)

    cache_shapes = jax.eval_shape(lambda: bundle.init_cache(batch, shape.seq_len))
    cspecs = SH.cache_specs(cache_shapes, plan, batch=batch,
                            seq_lens=(shape.seq_len, 2048))
    csh = SH.to_shardings(cspecs, mesh)

    all_batch_axes = tuple(list(plan.client_axes) + list(plan.micro_axes))
    tok_spec = P(SH._axes_entry(all_batch_axes) if batch > 1 else None, None)
    tok_sh = NamedSharding(mesh, tok_spec)

    def serve_step(params, cache, tokens, position):
        return bundle.decode_step(params, cache, tokens, position)

    tok_shape = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos_shape = jax.ShapeDtypeStruct((), jnp.int32)
    fn = jax.jit(serve_step, in_shardings=(psh, csh, tok_sh, rep),
                 out_shardings=(rep, csh))
    return fn, (params_shapes, cache_shapes, tok_shape, pos_shape), plan


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
             "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"=\s*\(?([a-z0-9]+)\[([\d,]*)\]")
_COLL_RE = re.compile(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start|-done)?\(")


def _line_collective(stripped: str):
    m = _COLL_RE.search(stripped)
    if not m or m.group(2) == "-done":
        return None
    sm = _SHAPE_RE.search(stripped)
    if not sm:
        return None
    n = 1
    for d in sm.group(2).split(","):
        if d:
            n *= int(d)
    return m.group(1), n * _DT_BYTES.get(sm.group(1), 4)


def _parse_computations(hlo_text: str) -> dict:
    """name -> body text. Computations end with a column-0 '}' line."""
    comps = {}
    cur, buf = None, []
    for line in hlo_text.splitlines():
        if cur is None:
            m = re.match(r"(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{", line)
            if m:
                cur = m.group(1)
                buf = []
                if line.startswith("ENTRY"):
                    comps["__entry__"] = cur
        elif line.startswith("}"):
            comps[cur] = buf
            cur = None
        else:
            buf.append(line.strip())
    return comps


def _trip_count(cond_body) -> int:
    """Largest s32 constant in the while condition ~= trip count (scan loops
    are canonical 0..N step 1)."""
    best = 1
    for line in cond_body:
        for m in re.finditer(r"s32\[\] constant\((\d+)\)", line):
            best = max(best, int(m.group(1)))
    return best


def collective_bytes(hlo_text: str) -> dict:
    """Loop-aware collective-byte accounting.

    XLA's cost_analysis counts while-loop bodies ONCE (verified in
    tests/test_roofline.py), so a naive sum over the HLO undercounts scanned
    layers. Here we walk the computation call graph from ENTRY, multiplying
    each while body by its trip count (recovered from the loop condition).
    """
    comps = _parse_computations(hlo_text)
    entry = comps.get("__entry__")
    out = {k: 0 for k in _COLLECTIVES}
    seen_stack = set()

    def walk(name: str, mult: float):
        if name not in comps or name in seen_stack:
            return
        seen_stack.add(name)
        for line in comps[name]:
            lc = _line_collective(line)
            if lc:
                out[lc[0]] += int(lc[1] * mult)
            wm = re.search(r"while\(.*?\), condition=%?([\w.\-]+), "
                           r"body=%?([\w.\-]+)", line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                walk(body, mult * trips)
                continue
            for cm in re.finditer(r"(?:calls|to_apply|body|condition|"
                                  r"branch_computations)=\{?%?([\w.\-]+)", line):
                walk(cm.group(1), mult)
        seen_stack.discard(name)

    if entry:
        walk(entry, 1.0)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def collective_bytes_naive(hlo_text: str) -> dict:
    """Flat sum (what cost_analysis effectively sees) — kept for the
    methodology comparison in EXPERIMENTS.md."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        lc = _line_collective(line.strip())
        if lc:
            out[lc[0]] += lc[1]
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def analyze(fn, arg_shapes, mesh, label: str) -> dict:
    t0 = time.time()
    lowered = fn.lower(*arg_shapes)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    coll_naive = collective_bytes_naive(hlo)

    res = {
        "label": label,
        "devices": mesh.size,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # raw compiled-artifact numbers (per partitioned module; while-loop
        # bodies counted once — see roofline.py docstring)
        "hlo_flops_raw": float(cost.get("flops", 0.0)),
        "hlo_bytes_raw": float(cost.get("bytes accessed", 0.0)),
        # loop-aware collective accounting from the same HLO
        "collective_bytes_per_device": coll["total"],
        "collective_bytes_naive": coll_naive["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total" and v},
    }
    for attr in ("output_size_in_bytes", "temp_size_in_bytes",
                 "argument_size_in_bytes", "generated_code_size_in_bytes"):
        res[attr] = getattr(mem, attr, None)
    return res


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             agg_backend: str = "auto", encode_backend: str = "auto",
             cohort: str = "auto", adversary: str = "none",
             pipeline: str = None) -> dict:
    arch = get_arch(arch_id)
    shape = SHAPES[shape_name]
    bundle = build_model(arch.model)
    if shape_name == "long_500k" and not bundle.subquadratic:
        return {"label": f"{arch_id}/{shape_name}", "skipped":
                "full-attention arch: no sub-quadratic path (DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan0 = SH.make_plan(arch, shape, mesh)
    with mesh, sharding_hints(mesh, plan0.seq_axes, plan0.micro_axes):
        if shape.kind == "train":
            fn, args, plan = build_train_cell(arch, shape, mesh, agg_backend,
                                              encode_backend, cohort,
                                              adversary, pipeline)
        elif shape.kind == "prefill":
            fn, args, plan = build_prefill_cell(arch, shape, mesh)
        else:
            fn, args, plan = build_decode_cell(arch, shape, mesh)
        label = f"{arch_id}/{shape_name}/{'pod2x16x16' if multi_pod else '16x16'}"
        res = analyze(fn, args, mesh, label)
        res["plan"] = dataclasses.asdict(plan)

    from repro.launch import roofline as RF
    terms = RF.terms_for(arch, shape, plan,
                         res["collective_bytes_per_device"], multi_pod)
    secs = terms.seconds()
    res.update({
        "flops_per_device": terms.flops_per_dev,
        "hbm_bytes_per_device": terms.hbm_bytes_per_dev,
        "model_flops_total": terms.model_flops_total,
        "t_compute_s": secs["compute"],
        "t_memory_s": secs["memory"],
        "t_collective_s": secs["collective"],
        "dominant": terms.dominant(),
        "roofline_fraction": round(terms.roofline_fraction(), 4),
        "useful_ratio": round(terms.model_flops_total /
                              (terms.flops_per_dev * terms.devices + 1e-9), 4),
    })
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--agg-backend", default="auto",
                    choices=list(compression.AGG_BACKENDS))
    ap.add_argument("--encode-backend", default="auto",
                    choices=list(compression.ENCODE_BACKENDS))
    ap.add_argument("--cohort", default="auto",
                    help="cohort execution policy: auto | vmap | "
                         "stream(shard=K|auto[,unroll=U][,devices=D|auto]"
                         "[,feed=device|host])")
    ap.add_argument("--adversary", default="none", metavar="SPEC",
                    help="wire-level fault-injection policy compiled into "
                         "the train cell (none | sign_flip(f=..) | "
                         "byte_corrupt(f=..,p=..) | collude(f=..) | "
                         "dropout(f=..)) — proves attacks lower/compile on "
                         "the production mesh")
    ap.add_argument("--pipeline", default=None, metavar="SPEC",
                    help="full compression pipeline spec overriding the "
                         "arch default, e.g. 'cv|zsign_packed' or "
                         "'ef|topk(frac=0.01)' (grammar: docs/API.md) — "
                         "compiles stateful pipelines on the production "
                         "mesh")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for arch_id in archs:
        for shape_name in shapes:
            for mp in meshes:
                try:
                    res = run_cell(arch_id, shape_name, multi_pod=mp,
                                   agg_backend=args.agg_backend,
                                   encode_backend=args.encode_backend,
                                   cohort=args.cohort,
                                   adversary=args.adversary,
                                   pipeline=args.pipeline)
                except Exception as e:  # record the failure, keep sweeping
                    res = {"label": f"{arch_id}/{shape_name}/"
                           f"{'multi' if mp else 'single'}",
                           "error": f"{type(e).__name__}: {e}"}
                results.append(res)
                print(json.dumps(res, default=str))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)


if __name__ == "__main__":
    main()
