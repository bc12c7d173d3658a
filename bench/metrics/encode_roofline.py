"""encode_roofline: the client encode kernel's share of its HBM roofline,
in percent.

Layer: the encode (``kernels/zsign`` ``compress_rng_pallas``, reached through
``core/compression.SignCodec``). The least time is the round's least encode
bytes (``bench/work.encode_bytes``: every client reads its f32
pseudo-gradient and writes one bit per coordinate) at the HBM peak; the
measured time is the device time of the kernel's operations in the trace,
summed over the device planes. The kernel also runs threefry on
the VPU, and the v5e publishes no VPU peak, so the share is against bytes
alone. Moves ``client_tokens_per_s``.
"""
from bench import trace

#: the encode kernel as a v5e trace names it: a Pallas custom call that
#: takes the name of its jitted wrapper (``%zsign_encode_fused.11``), or of
#: the kernel should it be given ``name=``
NAMES = ("zsign_encode_fused", "compress_rng")
KERNEL = 'custom_call_target="tpu_custom_call"'


def is_encode(op):
    return any(n in op.name for n in NAMES) and KERNEL in op.meta


def read(ctx):
    t = sum(trace.op_seconds(ctx.trace, is_encode, ctx.win).values())
    if t <= 0:
        return None
    least = ctx.work["encode_bytes"] * ctx.rounds / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / t
