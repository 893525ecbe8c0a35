"""expert_roofline.train: the held experts' grouped GEMMs at the roofline over
their device time in the traced round.

Required work, from the cell's shapes (``harness.flops.matrices``, the
routed experts' matrices): each held expert takes k / E of the tokens (E the
router's published width), so in a round of C·steps local steps of B·S
tokens each of its gate, up and down products runs forward and again for
the activation gradient (2·m·n FLOPs a row each), and its adapters take
6·r·(m + n) a row (forward, activation and weight gradient); each eval batch
runs them forward once. Bytes: every pass reads each held expert's weights
once (bf16) and reads and writes its rows. The least time is the larger of
the FLOP and byte bounds at the chip's peaks.

Device time: the ops whose HLO names :data:`OPS` matches, the grouped GEMMs
as the chip's trace names them. The context does not carry the
configuration, so the reader loads its one cell. None where no such op ran.
"""

import re

from harness import flops, spec

CELL = "deepseek-v2.ep8.train.silo3.s4k"
OPS = re.compile(r"ragged")


def held_expert_work(cfg, tr):
    """(FLOPs, bytes) the held experts' products require in one round."""
    r = int(cfg["lora_rank"])
    tokens = tr["batch"] * tr["seq_len"]
    eval_tokens = tr["eval_batch"] * tr["seq_len"]
    steps = tr["clients"] * tr["local_steps"]
    runs = ((steps, tokens, 2, 3), (tr["eval_batches"], eval_tokens, 1, 1))
    fl = nb = 0.0
    for mat in flops.matrices(cfg):
        if not mat.expert:
            continue
        m, n = mat.m, mat.n
        for count, toks, passes, lora_passes in runs:
            rows = toks * mat.share * mat.count
            fl += count * rows * (passes * 2.0 * m * n
                                  + lora_passes * 2.0 * r * (m + n))
            nb += count * passes * (2.0 * m * n * mat.count
                                    + 2.0 * rows * (m + n))
    return fl, nb


def read(ctx):
    red = ctx.get("reduced")
    if red is None:
        return None
    s = sum(v for k, v in red.ops.items() if OPS.search(k))
    if s <= 0:
        return None
    cell = spec.load_cell(CELL)
    fl, nb = held_expert_work(cell.config, cell.traffic)
    return 100.0 * flops.min_seconds(fl, nb, ctx["peaks"]) / s
