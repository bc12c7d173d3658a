"""Device time by named phase (``bench/phases.py``) on a hand-made trace
whose answers are known, the set-up compile reader, and the window's
compiles at the tiny size."""
import time
from pathlib import Path

import pytest

from bench import harness, phases, trace
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]

SGD_BWD = ("jit(round_step)/while/body/closed_call/fed.client.sgd/"
           "transpose(jvp())/while/body/closed_call/checkpoint/"
           "rematted_computation/model.attn/dot_general")
SGD_FWD_VMAP = ("jit(round_step)/vmap(fed.client.sgd)/while/body/"
                "closed_call/jvp(model.attn)/dot_general")


def _op(name, s, e):
    return trace.Op(name, s, e, f"{name} = f32[8] fusion(...)")


HAND = trace.Trace(
    ops={"/device:TPU:0": trace.nest([
             _op("%while.1", 1.0, 5.0),
             _op("%fusion.1", 1.0, 2.0),
             _op("%fusion.2", 2.0, 2.5),
             _op("%fusion.3", 2.5, 3.0),
             _op("%compress_rng.4", 3.0, 3.5),
             _op("%sign_reduce.5", 3.5, 4.5),
             _op("%copy.6", 4.5, 5.0),
             _op("%fusion.7", 5.0, 5.5),
             _op("%all-reduce.8", 5.5, 6.5)]),
         "/device:TPU:1": trace.nest([_op("%fusion.1", 1.0, 3.0)])},
    host=[("bench.window", 1.0, 6.0)])
#: what ``phases.op_names`` reads from a trace: the ops' ``op_name`` paths
#: by plane (the copy XLA added has none)
NAMES = {"/device:TPU:0": {
             "%while.1": "jit(round_step)/while",
             "%fusion.1": SGD_BWD,
             "%fusion.2": SGD_FWD_VMAP,
             "%fusion.3": "jit(round_step)/while/body/fed.client.flatten/sub",
             "%compress_rng.4": "jit(round_step)/while/body/closed_call/"
                                "fed.client.encode/jit(zsign_encode_fused)/"
                                "compress_rng/pallas_call",
             "%sign_reduce.5": "jit(round_step)/while/body/closed_call/"
                               "fed.server.fold/jit(sign_reduce)/"
                               "sign_reduce/pallas_call",
             "%fusion.7": "jit(round_step)/fed.server.apply/sub",
             "%all-reduce.8": "jit(round_step)/shard_map/fed.server.psum/"
                              "psum"},
         "/device:TPU:1": {"%fusion.1": SGD_BWD}}
WIN = (1.0, 6.0)


def _phase(name, plane="/device:TPU:0", path=None):
    names = NAMES[plane] if path is None else {name: path}
    return phases.phase_of(_op(name, 0, 1), names)


def test_phase_of_matches_components_through_transformations():
    assert _phase("%fusion.1") == ("fed.client.sgd", "model.attn")
    assert _phase("%fusion.2") == ("fed.client.sgd", "model.attn")
    assert _phase("%compress_rng.4") == ("fed.client.encode", None)
    assert _phase("%copy.6") == (None, None)
    # a component, not a prefix or a substring: no phase here
    assert _phase("x", path="jit(f)/fed.client.sgd_extra/fedavg.py") == \
        (None, None)


def test_phase_of_takes_the_innermost_fed_phase():
    assert _phase("x", path="jit(f)/fed.server.fold/fed.server.psum/psum") \
        == ("fed.server.psum", None)


def test_phase_seconds_by_plane_clipped_to_the_window():
    def sec(phase):
        return phases.phase_seconds(HAND, phase, WIN, NAMES)
    assert sec("fed.client.sgd") == {"/device:TPU:0": 1.5,
                                     "/device:TPU:1": 2.0}
    assert sec("model.attn") == {"/device:TPU:0": 1.5, "/device:TPU:1": 2.0}
    assert sec("fed.server.fold") == {"/device:TPU:0": 1.0,
                                      "/device:TPU:1": 0.0}
    # the psum op runs from 5.5 to 6.5: half of it is in the window
    assert sec("fed.server.psum") == {"/device:TPU:0": 0.5,
                                      "/device:TPU:1": 0.0}


def test_phases_and_unphased_add_up_to_the_leaf_time():
    for plane in HAND.ops:
        total = trace.op_seconds(HAND, lambda o: True, WIN)[plane]
        parts = [phases.phase_seconds(HAND, p, WIN, NAMES)[plane]
                 for p in phases.FED + (phases.UNPHASED,)]
        assert sum(parts) == pytest.approx(total)
    # the while op holds others: only leaves count; the copy has no path
    assert phases.phase_seconds(HAND, phases.UNPHASED, WIN, NAMES) == {
        "/device:TPU:0": 0.5, "/device:TPU:1": 0.0}


def test_a_program_without_phases_is_all_unphased():
    bare = {d: {n: "jit(round_step)/while/body/add" for n in m}
            for d, m in NAMES.items()}
    assert phases.phase_seconds(HAND, phases.UNPHASED, WIN, bare) == \
        trace.op_seconds(HAND, lambda o: True, WIN)


def test_setup_compile_reads_the_compile_log():
    from repro.core.spans import COMPILES
    v = harness.load_metric(ROOT, "setup_compile_s").read(None)
    assert v == COMPILES.snapshot()["total_s"]


def test_the_window_compiles_nothing(tmp_path):
    """The tiny cell on the CPU: no compile between the window's first
    round and its last."""
    from repro.core.spans import COMPILES
    snaps = []

    def wrap(step):
        def call(*a):
            snaps.append(COMPILES.snapshot())
            return step(*a)
        return call

    root = tiny.make_root(tmp_path)
    hooks = harness.Hooks(skip_device_check=True,
                          peak={"bf16_flops_per_s": 1e12,
                                "hbm_bytes_per_s": 1e11},
                          wrap_step=wrap)
    r = harness.run(root, "tiny.mix", 2 ** 31 + 11, 0.3, False,
                    time.perf_counter(), hooks)
    window = snaps[harness.CHECK_ROUNDS:]
    assert r["attempted"] == len(window) >= 2
    assert window[-1]["compiles"] == window[0]["compiles"]
    assert window[-1]["trace_s"] == window[0]["trace_s"]
