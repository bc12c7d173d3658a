"""``bench/calibrate_parts.py`` reads what ``bench/calibrate.py`` reads:
on four CPU devices (a process of its own, since the device count is fixed
when JAX starts), the tiny cell split over the devices gives the same
summary calibrated in parts, its reference on one device, as whole."""
import json
import os
import subprocess
import sys
from pathlib import Path

from bench import calibrate_parts


def test_parts_read_what_calibrate_reads():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(Path(__file__).with_name(
        "calibrate_parts_child.py"))], capture_output=True, text=True,
        timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(r["whole"]) == {"lower", "control", "half", "keys", "flip",
                               "exchange"}
    # the same compiled program and the same reference on the CPU: equal
    assert r["parts"] == r["whole"]


def test_one_chip_leaves_out_the_cohorts_devices():
    traffic = {"train_args": {"cohort": "stream(shard=1,devices=4)",
                              "clients": 4}}
    got, devices = calibrate_parts.one_chip(traffic)
    assert (got["train_args"]["cohort"], devices) == ("stream(shard=1)", 4)
    assert got["train_args"]["clients"] == 4
    assert traffic["train_args"]["cohort"] == "stream(shard=1,devices=4)"
    assert calibrate_parts.one_chip(
        {"train_args": {"cohort": "stream(devices=2)"}})[0][
            "train_args"]["cohort"] == "stream"
