"""The readings of ``bench/calibrate.py`` taken in parts, for a cell on
several chips: the program's first rounds on the cell's chips, every
reading of the reference on one chip, and the same summary from the saved
readings.

    python3 bench/calibrate_parts.py reference --workload qwen2-0.5b.silo4 \
        --seeds 11,12 --faults 1 --out chiprun_out/silo4     # on one chip
    python3 bench/calibrate_parts.py program --workload qwen2-0.5b.silo4 \
        --seeds 11,12 --out chiprun_out/silo4                # on the cell's
    python3 bench/calibrate_parts.py summary --workload qwen2-0.5b.silo4 \
        --seeds 11,12 --faults 1 --out chiprun_out/silo4     # anywhere

The reference runs on one chip whatever the cell runs on, so on a cell's
four chips ``bench/calibrate.py`` holds three of them idle while it reads
the reference, the control and the faults in turn. Here the reference part
builds the cell's rounds for one chip (the cohort's ``devices`` left out:
the reference reads nothing else of the plan) and plants the exchange
fault for the cell's own plan. Each reading is saved as
``<out>/<seed>.<kind>.npz``; ``summary`` prints what ``bench/calibrate.py``
prints for the same seeds and faults.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]

READING = ("loss", "grad", "change", "sign")


def save(out: Path, seed: int, kind: str, r: dict) -> None:
    import numpy as np
    out.mkdir(parents=True, exist_ok=True)
    np.savez(out / f"{seed}.{kind}.npz", **{k: np.asarray(r[k])
                                            for k in READING})


def load(out: Path, seed: int, kind: str):
    import numpy as np
    path = out / f"{seed}.{kind}.npz"
    if not path.is_file():
        return None
    with np.load(path) as f:
        return {k: f[k] for k in READING}


def one_chip(traffic: dict) -> tuple:
    """-> (the traffic with its cohort on one device, the cell's device
    count)."""
    from bench.program import spec_args
    cohort = traffic["train_args"]["cohort"]
    kw = spec_args(cohort)
    devices = int(kw.pop("devices", 1))
    spec = cohort.split("(")[0] + (
        "(" + ",".join(f"{k}={v}" for k, v in kw.items()) + ")" if kw
        else "")
    return {**traffic, "train_args": {**traffic["train_args"],
                                      "cohort": spec}}, devices


def program_part(root: Path, cell: str, seeds: list, out: Path,
                 emit=print) -> None:
    """The program's first rounds for each seed, through the cell's round
    step compiled once, on the cell's chips."""
    import numpy as np

    from bench import harness, inputs, program

    _, _, config, traffic = harness.find_cell(root, cell)
    harness.enable_compile_cache(root)
    prog = program.build(config, traffic, root)
    mask = program.mask(prog)
    compiled = None
    for seed in seeds:
        feed = lambda r: program.batch(prog, inputs.data_key(seed), r)
        state = program.init_state(prog, inputs.params(prog.shapes, seed),
                                   seed)
        if compiled is None:
            compiled = prog.step.lower(state, feed(0), mask).compile()
        state, got = harness.first_rounds(prog, compiled, state, feed, mask,
                                          seed)
        del state
        save(out, seed, "program", got)
        emit(json.dumps({"seed": seed, "kind": "program",
                         "loss": got["loss"],
                         "n_live": float(np.sum(np.asarray(mask)))}))


def reference_part(root: Path, cell: str, seeds: list, faults: int,
                   out: Path, emit=print) -> None:
    """The reference's first rounds for each seed and, for the first
    ``faults`` seeds, the control's and each fault's, on one chip."""
    import numpy as np

    from bench import calibrate, harness, program

    _, _, config, traffic = harness.find_cell(root, cell)
    harness.enable_compile_cache(root)
    traffic, devices = one_chip(traffic)
    prog = program.build(config, traffic, root)
    flat_mask = np.asarray(program.mask(prog)).reshape(-1)
    plan = SimpleNamespace(clients=prog.clients, plan=SimpleNamespace(
        shard=prog.plan.shard, devices=devices))
    read = lambda **kw: harness.reference_readings(config, traffic, prog,
                                                   seed, **kw)
    for i, seed in enumerate(seeds):
        kinds = {"reference": lambda: read(mask=flat_mask)}
        if i < faults:
            half = flat_mask.copy()
            half[len(half) // 2:] = 0.0
            kinds.update(
                control=lambda: read(precision="fp8", mask=flat_mask),
                half=lambda: read(mask=half),
                keys=lambda: read(mask=flat_mask, fault="keys"),
                flip=lambda: read(mask=flat_mask, fault="flip"))
            if devices > 1:
                kinds["exchange"] = lambda: read(
                    mask=flat_mask,
                    contributing=calibrate.first_chip_clients(plan))
        for kind, fn in kinds.items():
            t = time.perf_counter()
            r = fn()
            save(out, seed, kind, r)
            emit(json.dumps({"seed": seed, "kind": kind, "loss": r["loss"],
                             "s": time.perf_counter() - t}))


def summary(seeds: list, faults: int, out: Path, emit=print) -> dict:
    """What ``bench/calibrate.py`` prints for these seeds and faults, from
    the saved readings."""
    from bench import compare

    kinds = ("control", "half", "keys", "flip", "exchange")
    got = {k: [] for k in ("program",) + kinds}
    for i, seed in enumerate(seeds):
        ref = load(out, seed, "reference")
        stand_ins = {"program": load(out, seed, "program")}
        if i < faults:
            stand_ins.update({k: load(out, seed, k) for k in kinds})
        for kind, r in stand_ins.items():
            if r is None:
                continue
            nums = compare.numbers(r, ref)
            got[kind].append(nums)
            emit(json.dumps({"seed": seed, "kind": kind, **nums,
                             "loss": [float(x) for x in r["loss"]],
                             "ref_loss": [float(x) for x in ref["loss"]]}))
    result = {"lower": {n: max(r[n] for r in got["program"])
                        for n in compare.NAMES}}
    for kind in kinds:
        if got[kind]:
            result[kind] = {n: min(r[n] for r in got[kind])
                            for n in compare.NAMES}
    emit(json.dumps({"summary": result}))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("part", choices=("program", "reference", "summary"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--faults", type=int, default=1,
                    help="how many of the seeds also read the control and "
                         "the faults")
    ap.add_argument("--out", required=True, type=Path,
                    help="directory of the saved readings")
    opts = ap.parse_args(argv)
    seeds = [int(s) for s in opts.seeds.split(",")]
    emit = lambda s: print(s, flush=True)
    if opts.part == "summary":
        sys.path.insert(0, str(ROOT))
        summary(seeds, opts.faults, opts.out, emit)
        return 0
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import harness
    try:
        _, cell, _, _ = harness.find_cell(ROOT, opts.workload)
        harness.device_check(
            jax.devices(), cell["chips"] if opts.part == "program" else 1,
            harness.load_json(ROOT / "bench" / "peaks.json"))
    except harness.NoChip as e:
        print(f"calibrate_parts: {e}", file=sys.stderr)
        return 1
    if opts.part == "program":
        program_part(ROOT, opts.workload, seeds, opts.out, emit)
    else:
        reference_part(ROOT, opts.workload, seeds, opts.faults, opts.out,
                       emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
