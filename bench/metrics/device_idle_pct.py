"""device_idle_pct: the share of the traced window in which no operation
runs on the device, in percent, averaged over the chips.

Layer: the device. 1 - (union of the operations' intervals / window), over
a window of whole rounds. Moves ``client_tokens_per_s``.
"""
from bench import trace


def read(ctx):
    if ctx.win_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - trace.mean(ctx.busy.values()) / ctx.win_s)
