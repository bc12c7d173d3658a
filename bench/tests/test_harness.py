"""A whole run of the harness at a tiny size on the CPU, with the look for a
chip skipped: it is correct, it finds what a cell names by name, and its
measurement path refuses any device but a TPU it has peaks for."""
import filecmp
import json
import math
import time
from types import SimpleNamespace

import pytest

from bench import harness
from bench.tests import tiny

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
HOOKS = harness.Hooks(skip_device_check=True, peak=PEAK)


def test_tiny_cell_is_correct(tmp_path):
    root = tiny.make_root(tmp_path)
    r = harness.run(root, "tiny.mix", 2 ** 31 + 77, 0.5, False,
                    time.perf_counter(), HOOKS)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"client_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
    # the compiled round's bytes, where the runtime's counter gives less
    assert r["device"]["memory_peak_bytes"] > 0
    for name, c in r["checks"].items():
        assert c["value"] <= c["limit"], name
    json.dumps(r)


def test_new_config_mix_and_metric_need_no_edit(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files (and entries of BENCHMARK.json) are found by name; every file the
    benchmark already had is left as it was."""
    root = tiny.make_root(tmp_path)
    name = "tmp_rounds_traced"
    (root / "bench" / "metrics" / f"{name}.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": name, "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "host loop",
        "moves": "client_tokens_per_s", "workloads": ["tiny.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = harness.run(root, "tiny.mix", 5, 0.2, True, time.perf_counter(),
                    HOOKS)
    assert r["correct"]
    assert r["metrics"][name] == {"value": float(r["attempted"]),
                                  "unit": "rounds"}
    assert "round_gap_ms" in r["metrics"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    _unchanged(root)


#: a model family added as a new file: the dense loss, a check of its own
#: and a FLOP count no other family gives
TINYFAM = '''"""A model family for the tests."""
from bench.reference import dense

loss = dense.loss
FLOPS = 1234567.0


def check(config, model, program_facts):
    if config.get("tinyfam_refuses"):
        return {"tinyfam_refuses": (True, False)}
    return dense.check(config, model, program_facts)


def flops_per_token(config, shapes, seq):
    return FLOPS
'''


def _unchanged(root):
    cmp = filecmp.dircmp(tiny.ROOT / "bench", root / "bench",
                         ignore=["__pycache__"])
    for sub in [cmp] + list(cmp.subdirs.values()):
        assert not sub.diff_files, sub.diff_files


def test_new_family_needs_no_edit(tmp_path):
    """A model family added as a new module under bench/reference/, and a
    configuration that names it, are found by name: the run takes its
    reference, its configuration check and its FLOP count from the new
    module, and every file the benchmark already had is left as it was."""
    root = tiny.make_root(tmp_path)
    (root / "bench" / "reference" / "tinyfam.py").write_text(TINYFAM)
    tiny.add_cell(root, "tinyfam.mix",
                  dict(tiny.tiny_config("tinyfam-qwen2"), reference="tinyfam"),
                  "mix")
    r = harness.run(root, "tinyfam.mix", 2 ** 31 + 15, 0.2, True,
                    time.perf_counter(), HOOKS)
    assert r["correct"], r["checks"]
    tokens_per_round = 4 * 2 * 1 * 16
    want = 100.0 * 1234567.0 * tokens_per_round * r["attempted"] / (
        r["device"]["window_s"] * PEAK["bf16_flops_per_s"])
    assert math.isclose(r["metrics"]["mfu_pct"]["value"], want,
                        rel_tol=1e-9)
    tiny.add_cell(root, "tinyfam.refused",
                  dict(tiny.tiny_config("tinyfam-refused"),
                       reference="tinyfam", tinyfam_refuses=True), "mix")
    with pytest.raises(ValueError, match=r"tinyfam\.py.*tinyfam_refuses"):
        harness.run(root, "tinyfam.refused", 1, 0.2, False,
                    time.perf_counter(), HOOKS)
    _unchanged(root)


@pytest.mark.parametrize("reference, message", [
    ("nofam", r"looked for .*/bench/reference/nofam\.py"),
    (None, r"\"reference\" is None, where .*/bench/reference/<reference>\.py"),
])
def test_config_naming_no_family_module_is_refused(tmp_path, reference,
                                                   message):
    root = tiny.make_root(tmp_path)
    config = tiny.tiny_config("tiny-nofam")
    if reference is None:
        del config["reference"]
    else:
        config["reference"] = reference
    tiny.add_cell(root, "nofam.mix", config, "mix")
    with pytest.raises(ValueError, match=message):
        harness.run(root, "nofam.mix", 1, 0.2, False, time.perf_counter(),
                    HOOKS)


def _dev(platform, kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_measurement_path_refuses_other_devices():
    peaks = harness.load_json(tiny.ROOT / "bench" / "peaks.json")
    assert harness.device_check([_dev("tpu")], 1, peaks) == \
        peaks["devices"]["TPU v5 lite"]
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.device_check([_dev("cpu", "cpu")], 1, peaks)
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.device_check([], 1, peaks)
    with pytest.raises(harness.NoChip, match="4 chips"):
        harness.device_check([_dev("tpu")], 4, peaks)
    with pytest.raises(harness.NoChip, match="TPU v9"):
        harness.device_check([_dev("tpu", "TPU v9")], 1, peaks)


def test_run_exits_nonzero_without_a_chip():
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, str(tiny.ROOT / "bench" / "run.py"), "--workload",
         "qwen2-0.5b.xdevice", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
