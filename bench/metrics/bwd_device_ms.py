"""Device self time per traced step of the fused step's backward: ops under
``transpose(`` in their name stack, which holds the remat recompute of the
forward too (``checkpoint/rematted_computation``), from the profiler trace,
per chip."""

import scopes

LAYER = "fused step"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
PART = "backward"


def read(ctx):
    ns = (scopes.device_by_scope(ctx) or {}).get(PART)
    return ns * 1e-6 / ctx["profiled_steps"] if ns else None
