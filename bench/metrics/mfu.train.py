"""mfu.train: the local steps' share of the chip's bf16 peak, end to end.

Tokens per second of the window times the FLOPs one training token requires
(``harness.flops.train_flops_per_token``: no W0 weight gradient, no
recomputation) over the peak. Eval and close time count against it.
"""


def read(ctx):
    tps = ctx["e2e"].get("train_tokens_per_s")
    if not tps:
        return None
    return 100.0 * tps * ctx["flops_per_token"] / ctx["peaks"]["bf16_flops_per_s"]
