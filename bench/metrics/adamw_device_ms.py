"""Device self time per traced step of the fused step's optimizer: ops under
the ``adamw`` name scope (the non-finite guard, the global norm and the
AdamW update), from the profiler trace, per chip.  Nothing where the
program's ops carry no such scope."""

import scopes

LAYER = "fused step"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
PART = "adamw"


def read(ctx):
    ns = (scopes.device_by_scope(ctx) or {}).get(PART)
    return ns * 1e-6 / ctx["profiled_steps"] if ns else None
