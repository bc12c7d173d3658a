"""expert_roofline: the held experts' grouped-matmul kernels' share of the
chip's bf16 MXU peak, in percent.

Layer: the expert layer's grouped matmuls (``models/layers.held_experts``:
megablox ``gmm`` for the forward and the input gradient, ``tgmm`` for the
weight gradient). The least time is the FLOPs of the rows a token routes to
the held experts on average, forward and backward, at the bf16 peak: the
configuration's family module's ``expert_flops_per_token``
(``bench/reference/mla_moe.py``) of the run's cell, times the window's
tokens. The run's cell is the one, among the cells this metric is listed
for, whose chips and tokens a round are the run's; where none or several
are, it reads nothing. The measured time
is the device time of the kernels' operations in the trace, summed over the
device planes. Where the trace holds no such kernel (the program has no
expert layer) it reads nothing. Moves ``client_tokens_per_s``.
"""
from pathlib import Path

from bench import harness, program, trace

NAME = "expert_roofline"
#: the kernels as a v5e trace names them: Pallas custom calls named after
#: megablox's jitted entry points, ``gmm`` and ``tgmm``, within the names of
#: the transforms that reach them (``%transpose_jvp_jit_tgmm___.1``)
KERNEL = 'custom_call_target="tpu_custom_call"'
ROOT = Path(__file__).resolve().parents[2]


def is_gmm(op):
    return "gmm" in op.name and KERNEL in op.meta


def tokens_per_round(traffic: dict) -> int:
    a = traffic["train_args"]
    return a.get("groups", 1) * a["clients"] * a["local-steps"] \
        * a["micro-batch"] * a["seq-len"]


def expert_flops_per_token(ctx, root: Path = ROOT):
    """The held experts' FLOPs a token in the run's cell, or None."""
    bench = harness.load_json(root / "BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    found = []
    for name in entry.get("workloads", []):
        _, cell, config, traffic = harness.find_cell(root, name)
        if (cell["chips"], tokens_per_round(traffic)) == \
                (ctx.chips, ctx.work["tokens_per_round"]):
            found.append(config)
    if len(found) != 1:
        return None
    family = program.load_family(found[0], root)
    count = getattr(family, "expert_flops_per_token", None)
    return None if count is None else count(found[0])


def read(ctx):
    t = sum(trace.op_seconds(ctx.trace, is_gmm, ctx.win).values())
    if t <= 0:
        return None
    per_token = expert_flops_per_token(ctx)
    if per_token is None:
        return None
    flops = per_token * ctx.work["tokens_per_round"] * ctx.rounds
    return 100.0 * flops / (ctx.peak["bf16_flops_per_s"] * t)
