"""setup_compile_s: host seconds the run spent tracing, lowering and
compiling, or loading compiled programs from the compile cache, before its
measured window ended: the round step and the weights' initialisation of
set-up, and the few small readers of the check's first rounds. The window
itself compiles nothing.

Layer: set-up compile. Read from the program's ``repro.core.spans.COMPILES``
log of ``jax.monitoring`` events (``trace_s + lower_s + compile_s``, where a
cache hit's load is its compile), which counts from the program's first
import; nothing where the program has no such log. Moves ``setup_s``.
"""


def read(ctx):
    try:
        from repro.core.spans import COMPILES
    except ImportError:
        return None
    return COMPILES.snapshot()["total_s"]
