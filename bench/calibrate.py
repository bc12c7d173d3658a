"""The readings a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload qwen2-0.5b.xdevice \
        --seeds 11,12,13 --faults 3

For every seed: the program's first rounds (through the cell's compiled
round step, compiled once) against the reference's: the lower readings.
For the first ``--faults`` seeds also, each against the same reference:

  control   the reference computed with fp8 contractions (the precision
            below the configuration's bf16), its weights held in bf16, in
            the program's place;
  half      half of the clients left out of the round, the mean taken over
            the rest;
  keys      every client encoded under a key that is not the round's;
  flip      the server's update applied with the wrong sign;
  exchange  (cells on several chips) only the first chip's clients reach
            the server sum, as if the psum between chips were left out.

A state left unchanged reads 1 on ``change_gap`` by construction and needs
no run. One JSON line per reading, then a summary line: for each number its
lower reading (largest over the program's seeds) and, per stand-in, its
smallest reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def first_chip_clients(prog) -> list:
    """0/1 per client: 1 for the clients the first chip of the cohort plan
    runs (contiguous slices of shards, one slice per chip)."""
    total, shard, chips = prog.clients, prog.plan.shard, prog.plan.devices
    n_shards = -(-total // shard)
    n_shards = -(-n_shards // chips) * chips
    first = shard * (n_shards // chips)
    return [1.0 if j < first else 0.0 for j in range(total)]


def readings(root: Path, cell: str, seeds: list, faults: int,
             emit=print) -> dict:
    import jax
    import numpy as np

    from bench import compare, harness, inputs, program

    _, _, config, traffic = harness.find_cell(root, cell)
    harness.enable_compile_cache(root)
    prog = program.build(config, traffic, root)
    mask = program.mask(prog)
    flat_mask = np.asarray(mask).reshape(-1)
    kinds = ("control", "half", "keys", "flip", "exchange")
    compiled, out = None, {k: [] for k in ("program",) + kinds}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        dkey = inputs.data_key(seed)
        feed = lambda r: program.batch(prog, dkey, r)
        state = program.init_state(prog, inputs.params(prog.shapes, seed),
                                   seed)
        if compiled is None:
            compiled = prog.step.lower(state, feed(0), mask).compile()
        state, got = harness.first_rounds(prog, compiled, state, feed, mask,
                                          seed)
        del state
        ref = harness.reference_readings(config, traffic, prog, seed,
                                         mask=flat_mask)
        stand_ins = {"program": got}
        if i < faults:
            stand_ins["control"] = harness.reference_readings(
                config, traffic, prog, seed, precision="fp8", mask=flat_mask)
            half = flat_mask.copy()
            half[len(half) // 2:] = 0.0
            stand_ins["half"] = harness.reference_readings(
                config, traffic, prog, seed, mask=half)
            for fault in ("keys", "flip"):
                stand_ins[fault] = harness.reference_readings(
                    config, traffic, prog, seed, mask=flat_mask, fault=fault)
            if prog.plan.devices > 1:
                stand_ins["exchange"] = harness.reference_readings(
                    config, traffic, prog, seed, mask=flat_mask,
                    contributing=first_chip_clients(prog))
        for kind, r in stand_ins.items():
            nums = compare.numbers(r, ref)
            out[kind].append(nums)
            emit(json.dumps({"seed": seed, "kind": kind, **nums,
                             "loss": r["loss"], "ref_loss": ref["loss"]}))
        emit(f"# seed {seed}: {time.perf_counter() - t:.1f} s")
    summary = {"lower": {n: max(r[n] for r in out["program"])
                         for n in compare.NAMES}}
    for kind in kinds:
        if out[kind]:
            summary[kind] = {n: min(r[n] for r in out[kind])
                             for n in compare.NAMES}
    emit(json.dumps({"summary": summary}))
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--faults", type=int, default=3,
                    help="how many of the seeds also read the control and "
                         "the faults")
    opts = ap.parse_args(argv)
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import harness
    try:
        bench, cell, _, _ = harness.find_cell(ROOT, opts.workload)
        harness.device_check(jax.devices(), cell["chips"], harness.load_json(
            ROOT / "bench" / "peaks.json"))
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    readings(ROOT, opts.workload, [int(s) for s in opts.seeds.split(",")],
             opts.faults, emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
