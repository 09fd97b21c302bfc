"""90th percentile of the wall time of the window's steps (each ends when
its metrics are back on the host)."""

from quantiles import pct

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "useful_tokens_per_s"


def read(ctx):
    return 1e3 * pct(ctx["steps_s"], 90) if ctx["steps_s"] else None
