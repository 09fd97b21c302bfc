"""Device time per traced step of the fused step's XLA program
(``jit_step_fn``), from the profiler trace, per chip."""

LAYER = "fused step"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
MODULE = "jit_step_fn"


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    ns = sum(v for name, v in red["modules_ns"].items() if name.startswith(MODULE))
    return ns * 1e-6 / ctx["profiled_steps"] if ns > 0 else None
