"""close_roofline.train: the close's required work at the roofline over the
device time of the close programs in the traced round.

Required work (``harness.flops.close_work``): read and write every adapted
W0 once, read the clients' factors, 2·m·n·r·(C+1) FLOPs; the least time is
the larger of the byte and FLOP bounds at the chip's peaks.
"""

from harness.flops import min_seconds


def read(ctx):
    red = ctx.get("reduced")
    if red is None:
        return None
    s, n = red.program_seconds(ctx["close_programs"])
    if not n or s <= 0:
        return None
    need = min_seconds(ctx["close_flops"], ctx["close_bytes"], ctx["peaks"])
    return 100.0 * need * n / s
