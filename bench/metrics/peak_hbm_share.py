"""``peak_bytes_in_use`` over ``bytes_limit`` of the fullest chip after the
window (the device's own allocator counters)."""

LAYER = "device"
UNIT = "%"
MOVES = "useful_tokens_per_s"


def read(ctx):
    mem = ctx["memory"]
    if not mem["bytes_limit"] or not mem["peak_bytes_in_use"]:
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
