"""reduce_roofline: the server sign-reduce kernel's share of its HBM
roofline, in percent.

Layer: the server reduce (``kernels/zsign`` ``sign_reduce_pallas``). The
least time is the round's least reduce bytes (``bench/work.reduce_bytes``:
read every live client's payload, write the f32 sum once) at the HBM peak;
the measured time is the device time of the kernel's operations in the
trace, summed over the device planes. Moves ``client_tokens_per_s``.
"""
from bench import trace

#: the reduce kernel as a v5e trace names it: the Pallas custom call
#: ``%sign_reduce.11``
NAME = "sign_reduce"
KERNEL = 'custom_call_target="tpu_custom_call"'


def is_reduce(op):
    return NAME in op.name and KERNEL in op.meta


def read(ctx):
    t = sum(trace.op_seconds(ctx.trace, is_reduce, ctx.win).values())
    if t <= 0:
        return None
    least = ctx.work["reduce_bytes"] * ctx.rounds / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / t
