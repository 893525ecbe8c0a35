"""mfu.serve: the whole served close's share of the chip's peak: the close's
required least time (``harness.flops``) over the window's mean close wait
(last uplink sent → first pull of the new version back)."""

from harness.flops import min_seconds


def read(ctx):
    wait = ctx["e2e"].get("close_wait_ms")
    if not wait:
        return None
    need = min_seconds(ctx["close_flops"], ctx["close_bytes"], ctx["peaks"])
    return 100.0 * need / (wait / 1e3)
