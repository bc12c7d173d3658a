"""Record ``data/small_trace.xplane.pb`` on a TPU host: three traced rounds
of the tiny cell, split over every chip of the host (on four chips it then
holds the psum between them).

    python3 bench/tests/record_small_trace.py <output .xplane.pb>
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402


def main(out: str) -> int:
    import jax
    chips = len(jax.devices())
    cohort = f"stream(shard=1,devices={chips})" if chips > 1 else \
        "stream(shard=1)"
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(Path(tmp) / "root", chips=chips, cohort=cohort)
        r = harness.run(root, "tiny.mix", 7, 0.2, True, time.perf_counter(),
                        trace_dir=str(Path(tmp) / "trace"))
        src = sorted((Path(tmp) / "trace").rglob("*.xplane.pb"))[-1]
        shutil.copy(src, out)
    print(r["metrics"], r["device"], r["breakdown"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
