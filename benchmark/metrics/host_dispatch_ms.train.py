"""Mean host time inside one ``FusedTrainer.step`` call, from the
benchmark's own span around each call (program_span)."""


def read(ctx):
    r = ctx["record"]
    if not r.get("steps"):
        return None
    return 1e3 * r["step_call_s"] / r["steps"]
