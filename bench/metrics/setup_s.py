"""setup_s: host seconds from the start of the process to the end of the
warm-up round: imports, device start, weights made on the device, the
round step compiled or loaded from the compile cache, and one round.
"""


def read(ctx):
    return ctx.setup_s
