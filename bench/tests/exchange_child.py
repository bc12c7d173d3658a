"""Run by test_exchange.py in a process of its own, with four CPU devices:
the tiny cell split over four devices, intact and with the exchange between
the devices left out. Prints one JSON line: {"intact": ..., "broken": ...}."""
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

HOOKS = harness.Hooks(skip_device_check=True,
                      peak={"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e11})


def once(broken: bool) -> dict:
    from repro.core import wire
    keep = wire.psum_accumulator
    if broken:
        wire.psum_accumulator = lambda acc, axis_name: acc
    try:
        with tempfile.TemporaryDirectory() as tmp:
            root = tiny.make_root(Path(tmp), chips=4,
                                  cohort="stream(shard=1,devices=4)")
            r = harness.run(root, "tiny.mix", 2 ** 32 - 5, 0.2, False,
                            time.perf_counter(), HOOKS)
    finally:
        wire.psum_accumulator = keep
    return {"correct": r["correct"], "checks": r["checks"]}


if __name__ == "__main__":
    print(json.dumps({"intact": once(False), "broken": once(True)}))
