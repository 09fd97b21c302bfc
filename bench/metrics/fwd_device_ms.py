"""Device self time per traced step of the fused step's forward: ops under
the model's name scopes (``embed``, ``layers``, ``head_loss``) and not under
``transpose(``, from the profiler trace, per chip.  Nothing where the
program's ops carry no model scope."""

import scopes

LAYER = "fused step"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
PART = "forward"


def read(ctx):
    ns = (scopes.device_by_scope(ctx) or {}).get(PART)
    return ns * 1e-6 / ctx["profiled_steps"] if ns else None
