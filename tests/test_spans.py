"""The round's named phases (core/spans.py) reach the compiled program, and
the compile log counts compiles.

A phase is a ``jax.named_scope``: a component of the ``op_name`` metadata of
every op compiled inside it, backward and rematerialised ops included. A
transformation may wrap the component it meets first
(``vmap(fed.client.sgd)``), so components are compared unwrapped.
"""
import dataclasses
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import spans

HERE = Path(__file__).resolve().parent
MOE = tuple(p for p in spans.PHASES if p.startswith("model.moe."))
SRC = Path(spans.__file__).resolve().parents[2]

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 128, "vocab": 512}


def op_names(cohort: str, arch: str = "qwen2_0_5b") -> list:
    """[(instruction name, op_name path)] of the tiny round step's compiled
    HLO on the plan ``cohort``: qwen2 at the sizes of ``TINY``, or another
    registry entry reduced."""
    from repro.configs.common import get_arch
    from repro.core import fedavg
    from repro.launch import train
    from repro.models.api import build_model

    args = train.parse_args([
        "--arch", arch, "--pipeline", "zsign_packed(z=1,sigma=0.01)",
        "--clients", "4", "--local-steps", "2", "--micro-batch", "1",
        "--seq-len", "16", "--cohort", cohort])
    model = (dataclasses.replace(get_arch(arch).model, **TINY)
             if arch == "qwen2_0_5b" else get_arch(arch).reduced().model)
    bundle = build_model(model)
    comp = train.build_compressor(args)
    cfg = train.fed_config(args)
    ctx = fedavg.RoundContext(weights_are_mask=True, cohort=cohort)
    step = jax.jit(fedavg.build_round_step(bundle.loss_fn, comp, cfg, ctx))
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: fedavg.init_server_state(
        p, cfg, comp, jax.random.PRNGKey(1), sigma0=0.01), params)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 4, 2, 1, 16), jnp.int32)}
    mask = jax.ShapeDtypeStruct((1, 4), jnp.float32)
    txt = step.lower(state, batch, mask).compile().as_text()
    return re.findall(r'^\s*(?:ROOT )?%([\w.\-]+) = .*op_name="([^"]*)"',
                      txt, re.M)


def components(path: str) -> set:
    out = set()
    for c in path.split("/"):
        while (m := re.fullmatch(r"[\w\-]+\((.*)\)", c)):
            c = m.group(1)
        out.add(c)
    return out


@pytest.fixture(scope="module", params=["vmap", "stream(shard=1)"])
def compiled_ops(request):
    return op_names(request.param)


def test_every_phase_reaches_the_compiled_round(compiled_ops):
    found = set().union(*(components(n) for _, n in compiled_ops))
    # the psum runs only across devices: test_psum_carries_its_phase; the
    # expert layer's phases only in a model that has one:
    # test_expert_phases_reach_the_compiled_round
    assert set(spans.PHASES) - {"fed.server.psum"} \
        - set(MOE) <= found
    assert not set(MOE) & found


def test_expert_phases_reach_the_compiled_round():
    """The reduced Moonlight round: the expert layer's routing, grouped
    matmuls and shared experts each name their ops, forward, backward and
    recomputed, inside the client step, beside the latent attention."""
    ops = op_names("stream(shard=1)", "moonlight_16b_a3b")
    paths = [n for _, n in ops if n.startswith("jit(")]
    for p in MOE + ("model.attn",):
        mine = [n for n in paths if p in components(n)]
        assert any("transpose(jvp" in n for n in mine), p
        assert any("rematted_computation" in n for n in mine), p
        for n in mine:
            assert "fed.client.sgd" in components(n), n


def test_backward_and_recomputed_ops_keep_their_phase(compiled_ops):
    # whole paths only: a reduction's own computation names its ops bare
    paths = [n for _, n in compiled_ops if n.startswith("jit(")]
    backward = [n for n in paths if "transpose(jvp" in n]
    remat = [n for n in paths if "rematted_computation" in n]
    assert backward and remat
    for n in backward + remat:
        assert "fed.client.sgd" in components(n), n
    assert any("model.attn" in components(n) for n in backward)
    assert any("model.attn" in components(n) for n in remat)


def test_fed_phases_do_not_nest(compiled_ops):
    fed = [p for p in spans.PHASES if p.startswith("fed.")]
    for _, n in compiled_ops:
        assert len(components(n) & set(fed)) <= 1, n


def test_psum_carries_its_phase():
    """On four CPU devices (a process of its own: the device count is fixed
    when JAX starts), the cross-device all-reduce of stream(devices=4) is
    in ``fed.server.psum``."""
    code = ("import json, sys; sys.path[:0] = [sys.argv[1], sys.argv[2]]; "
            "from test_spans import op_names; "
            "print(json.dumps(op_names('stream(shard=1,devices=4)')))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code, str(HERE), str(SRC)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    ops = json.loads(p.stdout.strip().splitlines()[-1])
    reduces = [n for ins, n in ops if ins.startswith("all-reduce")]
    assert reduces
    for n in reduces:
        assert "fed.server.psum" in components(n), n


def test_phase_refuses_an_unknown_name():
    with pytest.raises(ValueError, match="unknown phase"):
        spans.phase("fed.client.nope")
    with pytest.raises(ValueError, match="unknown host span"):
        spans.host("fed.nope")
    with spans.phase("fed.client.sgd"), spans.host("fed.round", step=3):
        pass


def test_compile_log_counts_one_compile_per_new_function():
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + x)
    x = jnp.arange(8.0)
    before = spans.COMPILES.snapshot()
    f(x).block_until_ready()
    once = spans.COMPILES.snapshot()
    f(x).block_until_ready()
    twice = spans.COMPILES.snapshot()
    assert once["compiles"] - before["compiles"] == 1
    assert once["trace_s"] > before["trace_s"]
    assert once["total_s"] > before["total_s"]
    assert twice == once
