"""Host time per window step in the trainer's control plane: the program's
own ``step.resolve`` (simulated arrivals, decode) and ``step.observe``
(throughput estimate) wall-clock spans, summed over the window and divided
by its steps."""

LAYER = "trainer control plane (host)"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
SPANS = ("step.resolve", "step.observe")


def read(ctx):
    spans = [s for s in ctx.get("spans", ()) if s["name"] in SPANS]
    if not spans or not ctx["steps_s"]:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(ctx["steps_s"])
