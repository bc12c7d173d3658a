"""client_tokens_per_s: client training tokens of every round completed in
the measured window, over the window's wall time (host clock, from the
window's start to the last round's ``block_until_ready``), in tokens per
second. End to end: what a federation pays chip time for.
"""


def read(ctx):
    if ctx.elapsed <= 0 or ctx.rounds == 0:
        return None
    return ctx.work["tokens_per_round"] * ctx.rounds / ctx.elapsed
