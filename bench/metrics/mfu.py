"""Model FLOP/s utilisation of the window: the unique batch's forward and
backward FLOPs (three times the reference's ``forward_flops``: no
replication, no remat) of every window step, over the window's seconds, over chips x the bf16 peak of the
device kind."""

LAYER = "fused step"
UNIT = "%"
MOVES = "useful_tokens_per_s"


def read(ctx):
    if ctx.get("peaks") is None or not ctx["steps_s"]:
        return None
    rate = ctx["flops_per_step"] * len(ctx["steps_s"]) / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
