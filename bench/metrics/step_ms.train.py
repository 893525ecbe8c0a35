"""step_ms.train: device time per run of the jitted local-step program, from
the traced round's ``XLA Modules`` events."""


def read(ctx):
    red = ctx.get("reduced")
    if red is None:
        return None
    s, n = red.program_seconds([ctx["step_program"]])
    return 1e3 * s / n if n else None
