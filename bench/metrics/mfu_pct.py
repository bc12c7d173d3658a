"""mfu_pct: model FLOPs of the traced window's client tokens over the
window's length times the chips' bf16 peak, in percent.

Layer: the round step (``core/fedavg.build_round_step``) as a whole. FLOPs
per token are the configuration's family module's ``flops_per_token``
(``bench/reference/<reference>.py``), with nothing counted for recompute.
Moves ``client_tokens_per_s``.
"""


def read(ctx):
    if ctx.win_s <= 0:
        return None
    flops = ctx.work["flops_per_token"] * ctx.work["tokens_per_round"] \
        * ctx.rounds
    return 100.0 * flops / (ctx.win_s * ctx.chips
                            * ctx.peak["bf16_flops_per_s"])
