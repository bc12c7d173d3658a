"""psum_exposed_ms: per round, the device time of the cross-chip all-reduce
during which no other operation runs on that device, in milliseconds,
averaged over the chips.

Layer: the cross-chip reduce (the ``lax.psum`` of ``fedavg.stream_cohort``'s
shard_map). Found only where a collective runs: cells on one chip have
none, and the reader returns nothing. Moves ``client_tokens_per_s``.
"""
from bench import trace

#: the collective's operations, as the trace names them (``%all-reduce.3``,
#: or the ``-start``/``-done`` pair of an asynchronous one)
MARK = "all-reduce"


def is_coll(op):
    return MARK in op.name


def read(ctx):
    if not any(is_coll(o) for ops in ctx.trace.ops.values() for o in ops):
        return None
    per = trace.exposed(ctx.trace, is_coll, ctx.win)
    return 1000.0 * trace.mean(per.values()) / ctx.rounds
