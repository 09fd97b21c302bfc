"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (window), averaged over chips."""

LAYER = "device"
UNIT = "%"
MOVES = "useful_tokens_per_s"


def read(ctx):
    red = ctx.get("trace")
    if red is None or red["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
