"""Host time per window step the training loop waited for its next batch:
the program's ``prefetch.wait`` wall-clock spans around the prefetch queue,
summed over the window and divided by its steps."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
SPANS = ("prefetch.wait",)


def read(ctx):
    spans = [s for s in ctx.get("spans", ()) if s["name"] in SPANS]
    if not spans or not ctx["steps_s"]:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(ctx["steps_s"])
