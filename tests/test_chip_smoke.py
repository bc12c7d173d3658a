"""chip_smoke.py off the chip: it refuses to report without a TPU, and its
backend comparison runs (and agrees) at a reduced width on the CPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import train

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, env=env, timeout=120)


def test_exits_nonzero_without_a_tpu():
    out = _run(ROOT / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not a TPU" in out.stderr


def test_exits_nonzero_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run(tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_backend_comparison_at_reduced_width(monkeypatch, capsys):
    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "TRAIN_ARGV",
                        smoke.TRAIN_ARGV[:2] + ["--reduced"]
                        + smoke.TRAIN_ARGV[2:-1] + ["16"])
    monkeypatch.setattr(smoke, "REDUCE_CHUNK_BYTES", 4096)
    smoke.backends_agree(jax, train)
    out = capsys.readouterr().out
    assert out.count("0 differ between pallas and jnp") == 5, out


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it the
    cache is the fixed <root>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        assert train.enable_compile_cache(tmp_path) == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(tmp_path / ".jax_cache")
        assert train.enable_compile_cache(tmp_path) == want
        assert jax.config.jax_compilation_cache_dir == want
        assert train.REPO_ROOT == ROOT
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
