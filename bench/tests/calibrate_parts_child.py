"""Run by test_calibrate_parts.py in a process of its own, with four CPU
devices: the tiny cell split over four devices, calibrated whole and in
parts. Prints one JSON line: {"whole": summary, "parts": summary}."""
import json
import sys
import tempfile
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import calibrate, calibrate_parts  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEEDS = [2 ** 32 - 5, 7]

if __name__ == "__main__":
    quiet = lambda s: None
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(Path(tmp), chips=4,
                              cohort="stream(shard=1,devices=4)")
        whole = calibrate.readings(root, "tiny.mix", SEEDS, 1, emit=quiet)
        out = Path(tmp) / "readings"
        calibrate_parts.program_part(root, "tiny.mix", SEEDS, out, quiet)
        calibrate_parts.reference_part(root, "tiny.mix", SEEDS, 1, out,
                                       quiet)
        parts = calibrate_parts.summary(SEEDS, 1, out, quiet)
    print(json.dumps({"whole": whole, "parts": parts}))
