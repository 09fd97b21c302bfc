"""Unique-batch tokens (k·mb·seq per step) of every window step that
applied an update, over the window's wall time (its start to the end of its
last step): all the work over all the time.  A skipped step counts zero."""

UNIT = "tokens/s"


def read(ctx):
    steps = len(ctx["steps_s"])
    if not steps or ctx["window_s"] <= 0:
        return None
    return (steps - ctx["skipped"]) * ctx["tokens_per_step"] / ctx["window_s"]
