"""Stable names for the round's phases, on the device and on the host, and a
log of the process's compiles.

Device phases are ``jax.named_scope``s: a component of every op's
``op_name`` metadata (``jit(step)/fed.client.sgd/transpose(jvp())/...``), at
no cost on the device. Host spans are profiler annotations, in the
profiler's own trace on the clock of the device ops. Neither records
anything unless a profiler is running.
"""
from __future__ import annotations

import threading

import jax
from jax import monitoring

#: device phases; the ``fed.*`` ones do not nest in each other, ``model.*``
#: ones are sub-phases of ``fed.client.sgd`` (``model.moe.*``: the expert
#: layer's routing and sort, its held experts' grouped matmuls, its shared
#: experts)
PHASES = ("fed.client.sgd", "fed.client.flatten", "fed.client.encode",
          "fed.server.fold", "fed.server.psum", "fed.server.apply",
          "model.attn", "model.moe.route", "model.moe.experts",
          "model.moe.shared")
#: host spans of ``launch/train.main``
HOST_SPANS = ("fed.round", "fed.feed", "fed.compile", "fed.checkpoint")


def phase(name: str):
    """``jax.named_scope(name)`` for a phase of ``PHASES``."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; the phases are {PHASES}")
    return jax.named_scope(name)


def host(name: str, step=None):
    """A host span of ``HOST_SPANS``; with ``step``, a step annotation."""
    if name not in HOST_SPANS:
        raise ValueError(f"unknown host span {name!r}; the spans are "
                         f"{HOST_SPANS}")
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


#: jax.monitoring duration events -> the seconds they add to. A persistent
#: cache hit is a backend compile event too, whose time is the cache load.
SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/core/compile/backend_compile_duration": "compile_s",
           "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s"}
COUNTS = {"/jax/core/compile/backend_compile_duration": "compiles",
          "/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}


class CompileLog:
    """Counts and seconds of the compiles of this process, from the moment the
    log is made. ``snapshot()`` returns them, with ``total_s`` (trace + lower
    + compile or cache load); take the difference of two snapshots."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals = dict.fromkeys([*SECONDS.values(), *COUNTS.values()],
                                     0)
        monitoring.register_event_listener(
            lambda event, **_: self._record(event, 0.0))
        monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: self._record(event, secs))

    def _record(self, event: str, secs: float):
        with self._lock:
            if event in SECONDS:
                self._totals[SECONDS[event]] += secs
            if event in COUNTS:
                self._totals[COUNTS[event]] += 1

    def snapshot(self) -> dict:
        with self._lock:
            s = dict(self._totals)
        s["total_s"] = s["trace_s"] + s["lower_s"] + s["compile_s"]
        return s


#: the process's log, its listeners registered once, as this module is first
#: imported: the round engine imports it, so every compile from the building
#: of a round step on is counted
COMPILES = CompileLog()
