"""idle_share.serve: 1 − device busy time (union of the ops' intervals) over
the traced rounds' window, in percent."""


def read(ctx):
    red = ctx.get("reduced")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
