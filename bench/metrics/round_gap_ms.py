"""round_gap_ms: host time between rounds, in milliseconds per round.

Layer: the host loop (the harness loop, standing where ``launch/train``'s
loop stands). For each pair of consecutive rounds of the traced window, the
host clock from the return of one round's ``block_until_ready`` to the
dispatch of the next round's step: preparing the batch and the mask, and
reading the loss. Moves ``client_tokens_per_s``: the device waits for it.
"""


def read(ctx):
    if not ctx.gaps:
        return None
    return 1000.0 * sum(ctx.gaps) / len(ctx.gaps)
