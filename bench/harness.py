"""One run of one cell: set up the program's round step, drive its first
rounds, measure a window of rounds, check the first rounds against the
reference, and print the result.

Everything a cell names is found by name: the cell in ``BENCHMARK.json``,
its configuration at the ``file`` that ``BENCHMARK.json`` gives, its traffic
mix at ``bench/traffic/<traffic>.json``, every metric's reader at
``bench/metrics/<name>.py`` (a ``read(ctx)`` that returns a number, or None
where it finds nothing to read), the cell's limits at
``bench/limits/<cell>.json``, and the configuration's model family (its
reference, configuration check and FLOP count) at
``bench/reference/<reference>.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, NamedTuple, Optional

#: first rounds that set-up drives and the reference follows
CHECK_ROUNDS = 2
#: most rounds a traced window holds (at least 2 whole rounds are traced)
TRACE_ROUNDS = 3


class NoChip(RuntimeError):
    """The measurement path found no device it may measure on."""


class Hooks(NamedTuple):
    """What a test changes in a run: the look for a chip skipped, the timed
    path broken underneath, or the program's readings replaced."""
    skip_device_check: bool = False
    peak: Optional[dict] = None
    wrap_step: Optional[Callable] = None
    program_readings: Optional[Callable] = None


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def as_run(config: dict) -> dict:
    """The configuration as the program runs it: a key the program has no
    option for sits in the file under ``not_applied``, with its published
    value and the value that runs; the run's value is taken."""
    runs = {k: v["runs"] for k, v in config.get("not_applied", {}).items()}
    return {**config, **runs}


def find_cell(root: Path, name: str) -> tuple:
    """-> (BENCHMARK.json, the cell, its configuration, its traffic mix)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                       f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = as_run(load_json(root / entry["file"]))
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def load_metric(root: Path, name: str):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str) -> tuple:
    """-> (end-to-end entries, per-layer entries) this cell reports."""
    def here(m, pool=None):
        if "workloads" in m:
            return cell in m["workloads"]
        return pool is None or m["moves"] in pool
    e2e = [m for m in bench["end_to_end"] if here(m)]
    names = {m["name"] for m in e2e}
    return e2e, [m for m in bench["per_layer"] if here(m, names)]


def device_check(devices, chips: int, peaks: dict) -> dict:
    """The peaks of the device to measure on; NoChip off the TPU, with too
    few chips, or on a device the table of peaks does not hold."""
    if not devices or devices[0].platform != "tpu":
        raise NoChip("no TPU: the default device is "
                     f"{devices[0].platform if devices else None!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, {len(devices)} found")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks["devices"][kind]


def enable_compile_cache(root: Path) -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def setting(config: dict, traffic: dict, prog):
    import jax.numpy as jnp

    from bench.program import spec_args
    from bench.reference.round import Setting
    kw = spec_args(traffic["train_args"]["pipeline"])
    z = kw.get("z", "1")
    return Setting(clients=prog.clients, local_steps=prog.layout[2],
                   client_lr=prog.args.client_lr,
                   server_lr=prog.args.server_lr,
                   sigma=float(kw["sigma"]),
                   z=0 if z == "inf" else int(z),
                   store=jnp.dtype(config["torch_dtype"]))


def reference_readings(config, traffic, prog, seed: int, precision="f32",
                       contributing=None, mask=None, fault=None) -> dict:
    """The reference's first rounds from the run's weights, tokens and key."""
    import numpy as np

    from bench import inputs
    from bench.reference.round import Reference
    ref = Reference(prog.family, config, setting(config, traffic, prog),
                    precision, fault)
    dkey = inputs.data_key(seed)
    lead = (prog.clients,) + prog.layout[2:] + (prog.seq,)
    if mask is None:
        mask = np.ones(prog.clients, np.float32)
    return ref.run(inputs.params(prog.shapes, seed),
                   lambda t: inputs.tokens(dkey, t, prog.layout + (prog.seq,),
                                           prog.model.vocab).reshape(lead),
                   inputs.server_key(seed), mask, CHECK_ROUNDS,
                   inputs.signs_key(seed), contributing)


def first_rounds(prog, step, state, feed, mask, seed: int,
                 warmed=lambda m: None) -> tuple:
    """Drive ``step`` through the first rounds, the first of which warms
    it up (``warmed(metrics)`` is called when it has ended), and read what
    the reference is compared on: each round's loss, the per-leaf norms of
    the weights' change after the first round and after all of them, and
    the signs of the first round's change at the sampled coordinates.
    -> (state, readings)."""
    import jax
    import numpy as np

    from bench import compare, inputs
    losses = []
    for t in range(CHECK_ROUNDS):
        state, m = step(state, feed(t), mask)
        jax.block_until_ready((state, m))
        losses.append(float(m.loss))
        if t == 0:
            warmed(m)
            params0 = inputs.params(prog.shapes, seed)
            grad = np.asarray(compare.leaf_norms(state.params, params0))
            sign = compare.change_signs(state.params, params0,
                                        inputs.signs_key(seed))
            del params0
    change = np.asarray(compare.leaf_norms(state.params,
                                           inputs.params(prog.shapes, seed)))
    return state, {"loss": losses, "grad": grad.tolist(),
                   "change": change.tolist(), "sign": sign}


def compiled_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)


def run(root: Path, cell_name: str, seed: int, seconds: float, traced: bool,
        t0: float, hooks: Hooks = Hooks(), trace_dir=None) -> dict:
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import compare, inputs, program, trace, work

    bench, cell, config, traffic = find_cell(root, cell_name)
    peaks = load_json(root / "bench" / "peaks.json")
    limits = compare.load_limits(root, cell_name)
    e2e, per_layer = cell_metrics(bench, cell_name)
    devices = jax.devices()
    peak = hooks.peak if hooks.skip_device_check else \
        device_check(devices, cell["chips"], peaks)
    if not hooks.skip_device_check:
        enable_compile_cache(root)

    # ---- set-up: the one object the window drives -----------------------
    prog = program.build(config, traffic, root)
    dkey = inputs.data_key(seed)
    state = program.init_state(prog, inputs.params(prog.shapes, seed), seed)
    mask = program.mask(prog)
    feed = lambda t: program.batch(prog, dkey, t)
    compiled = prog.step.lower(state, feed(0), mask).compile()
    step = hooks.wrap_step(compiled) if hooks.wrap_step else compiled
    warm = {}

    def warmed(m):
        warm.update(setup_s=time.perf_counter() - t0,
                    uplink_bits=float(m.uplink_bits))

    state, readings = first_rounds(prog, step, state, feed, mask, seed,
                                   warmed)
    setup_s = warm["setup_s"]
    print(f"# cell {cell_name}: {prog.clients} clients x E={prog.layout[2]} x "
          f"{prog.layout[3]}x{prog.seq} tokens, d={work.n_params(prog.shapes)}"
          f", {prog.plan.devices} device(s)")
    print(f"# compiled round: {compiled_bytes(compiled)} bytes (arguments + "
          f"temp + outputs - aliased), {warm['uplink_bits']:.0f} uplink bits "
          f"per round")

    # ---- the measured window --------------------------------------------
    tmp = tempfile.TemporaryDirectory() if traced and not trace_dir else None
    trace_dir = trace_dir or (tmp.name if tmp else None)
    if traced:
        jax.profiler.start_trace(trace_dir)
    rounds, failed, gaps, t = 0, 0, [], CHECK_ROUNDS
    last = None
    with TraceAnnotation("bench.window"):
        start = time.perf_counter()
        while True:
            with TraceAnnotation("bench.prepare"):
                batch = feed(t)
            dispatch = time.perf_counter()
            if last is not None:
                gaps.append(dispatch - last)
            with TraceAnnotation("bench.dispatch"):
                state, m = step(state, batch, mask)
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready((state, m))
                last = time.perf_counter()
            loss = float(m.loss)
            rounds += 1
            failed += not math.isfinite(loss)
            t += 1
            if last - start >= seconds and (not traced or rounds >= 2):
                break
            if traced and rounds >= TRACE_ROUNDS:
                break
    elapsed = last - start
    used = devices[:cell["chips"]]
    # the runtime's peak leaves out the compiled round's temporaries, which
    # the compiled program holds on every chip it runs on: take the larger
    peak_bytes = max([compiled_bytes(compiled)] + [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used])

    ctx = SimpleNamespace(rounds=rounds, elapsed=elapsed, setup_s=setup_s,
                          gaps=gaps, chips=cell["chips"], peak=peak,
                          work={"tokens_per_round": prog.tokens_per_round})
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    breakdown = None
    if traced:
        jax.profiler.stop_trace()
        paths = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        tr = trace.load(paths[-1])
        if tmp:
            tmp.cleanup()
        win = trace.window(tr)
        ctx.trace, ctx.win, ctx.win_s = tr, win, win[1] - win[0]
        ctx.busy = {d: b for d, b in trace.busy(tr, win).items()
                    if int(d[len(trace.DEVICE_PREFIX):]) < len(used)}
        d = work.n_params(prog.shapes)
        ctx.work.update(
            flops_per_token=prog.family.flops_per_token(
                config, prog.shapes, prog.seq),
            encode_bytes=work.encode_bytes(prog.clients, d),
            reduce_bytes=work.reduce_bytes(int(np.sum(np.asarray(mask))), d))
        device.update(busy_s=trace.mean(ctx.busy.values()),
                      window_s=ctx.win_s)
        breakdown = {"device_ops": trace.top_ops(tr, win),
                     "idle_gaps": trace.idle_gaps(tr, win)}

    metrics = {}
    for m_ in (per_layer if traced else e2e):
        v = load_metric(root, m_["name"]).read(ctx)
        if v is not None:
            metrics[m_["name"]] = {"value": v, "unit": m_["unit"]}

    # ---- the check: the reference follows the first rounds ---------------
    del state, m, compiled, step, batch
    gc.collect()
    if hooks.program_readings is not None:
        readings = hooks.program_readings(config, traffic, prog, seed)
    t_ref = time.perf_counter()
    ref = reference_readings(config, traffic, prog, seed,
                             mask=np.asarray(mask).reshape(-1))
    values = compare.numbers(readings, ref)
    correct, checks = compare.judge(values, limits)
    correct = correct and failed == 0
    print(f"# program losses {readings['loss']}, reference "
          f"{ref['loss']}; reference took {time.perf_counter() - t_ref:.1f} s")
    result = {"correct": correct, "attempted": rounds, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
