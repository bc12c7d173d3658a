"""The reduction on a trace recorded on one v5e chip: ``data/small_trace.
xplane.pb``, three traced rounds of the tiny cell, written by
``record_small_trace.py`` (jax 0.9.0, libtpu 0.0.34), then cut under 1 MB:
the ``/host:metadata`` plane (the programs' HLO protos), the ops' ``source``
and ``source_stack`` stats and the host name taken out. It shows what the
chip's trace carries: the device ops of the ``XLA Ops`` line, each with its
``op_name`` path in its metadata, the named kernels, and the harness's host
spans."""
from pathlib import Path

import pytest

from bench import harness, phases, trace

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).with_name("data") / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def small():
    tr = trace.load(DATA)
    return tr, trace.window(tr), phases.op_names(DATA)


def test_busy_union_and_idle_share(small):
    tr, win, names = small
    assert list(tr.ops) == ["/device:TPU:0"]
    assert win[1] - win[0] == pytest.approx(9.703499e-3, rel=1e-6)
    busy = trace.busy(tr, win)["/device:TPU:0"]
    assert busy == pytest.approx(1.074438e-3, rel=1e-6)
    # a tiny round leaves the chip idle most of the window
    assert 1.0 - busy / (win[1] - win[0]) == pytest.approx(0.889273, abs=1e-6)
    # a loop op's own time between its body's ops counts as busy, not leaf
    leaf = trace.op_seconds(tr, lambda o: True, win)["/device:TPU:0"]
    assert leaf == pytest.approx(9.97795e-4, rel=1e-6)


def test_idle_gaps_are_named_by_the_harness_spans(small):
    tr, win, names = small
    gaps = trace.idle_gaps(tr, win)
    assert gaps
    assert {name for name, _ in gaps} <= {"bench.prepare", "bench.dispatch",
                                         "bench.wait", "other"}


@pytest.mark.parametrize("reader, match, seconds", [
    ("encode_roofline", "is_encode", 6.20030e-5),
    ("reduce_roofline", "is_reduce", 8.42630e-5)])
def test_kernel_time_by_name(small, reader, match, seconds):
    """The readers find the named kernels (``%compress_rng.11``,
    ``%sign_reduce.11``) in the phases they run in."""
    tr, win, names = small
    is_kernel = getattr(harness.load_metric(ROOT, reader), match)
    assert trace.op_seconds(tr, is_kernel, win)["/device:TPU:0"] == \
        pytest.approx(seconds, rel=1e-6)
    kernels = [o for o in tr.ops["/device:TPU:0"] if is_kernel(o)]
    assert {phases.phase_of(o, names["/device:TPU:0"])[0]
            for o in kernels} == {"fed.client.encode" if reader.startswith(
                "encode") else "fed.server.fold"}


@pytest.mark.parametrize("phase", [p for p in phases.FED + phases.MODEL
                                   if p != "fed.server.psum"])
def test_every_phase_on_one_chip_is_found(small, phase):
    tr, win, names = small
    assert phases.phase_seconds(tr, phase, win, names)["/device:TPU:0"] > 0.0


def test_phases_and_unphased_cover_the_leaf_time(small):
    tr, win, names = small
    total = trace.op_seconds(tr, lambda o: True, win)["/device:TPU:0"]
    parts = {p: phases.phase_seconds(tr, p, win, names)["/device:TPU:0"]
             for p in phases.FED + (phases.UNPHASED,)}
    assert sum(parts.values()) == pytest.approx(total)
    assert parts["fed.server.psum"] == 0.0
