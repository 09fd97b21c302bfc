"""Host time per window step before the device can start the step: the
program's ``phase.upload`` (the step's small inputs) and ``phase.dispatch``
(the jitted call until it returns) wall-clock spans, summed over the window
and divided by its steps.  Nothing where the program has no
``phase.dispatch`` span."""

LAYER = "step engine (host)"
UNIT = "ms"
MOVES = "useful_tokens_per_s"
SPANS = ("phase.upload", "phase.dispatch")


def read(ctx):
    spans = [s for s in ctx.get("spans", ()) if s["name"] in SPANS]
    if not any(s["name"] == "phase.dispatch" for s in spans) or not ctx["steps_s"]:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(ctx["steps_s"])
