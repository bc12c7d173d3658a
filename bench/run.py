"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload qwen2-0.5b.xdevice --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout that holds the program under ``src/``. It
measures on the chips of the machine it is started on and exits non-zero,
with no result, where JAX finds no TPU or fewer chips than the cell asks
for. ``--trace 1`` reports the cell's per-layer metrics from a profiler
trace of a few rounds instead of its end-to-end metrics. See PERF.md.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace of a --trace 1 run in this "
                         "directory (default: a temporary one, removed)")
    opts = ap.parse_args(argv)
    # the TPU runtime would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from bench import harness
    try:
        result = harness.run(ROOT, opts.workload, opts.seed, opts.seconds,
                             bool(opts.trace), T0,
                             trace_dir=opts.trace_dir)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    checks = result["checks"]
    print(json.dumps(result))
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
