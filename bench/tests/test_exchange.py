"""The exchange between chips left out: on four CPU devices (a process of
its own, since the device count is fixed when JAX starts), the tiny cell
split over the devices is correct, and not correct once the psum of the
wire sums is taken out."""
import json
import os
import subprocess
import sys
from pathlib import Path


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable,
                        str(Path(__file__).with_name("exchange_child.py"))],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["intact"]["correct"], r
    assert not r["broken"]["correct"], r
    assert r["broken"]["checks"]["grad_gap"]["value"] > \
        r["broken"]["checks"]["grad_gap"]["limit"]
