"""close_roofline.serve: as ``close_roofline.train``, over the traced served
rounds: one close's required least time times the rounds closed, over the
device time of every close program (stacked close, or partial folds and
finalize) in them."""

from harness.flops import min_seconds


def read(ctx):
    red = ctx.get("reduced")
    rounds = ctx.get("traced_rounds", 0)
    if red is None or not rounds:
        return None
    s, n = red.program_seconds(ctx["close_programs"])
    if not n or s <= 0:
        return None
    need = min_seconds(ctx["close_flops"], ctx["close_bytes"], ctx["peaks"])
    return 100.0 * need * rounds / s
