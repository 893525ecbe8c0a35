"""Tiny cells for CPU tests of the benchmark harness.

The harness and the generators are the ones ``bench/run.py`` uses; only the
chip check is skipped and the sizes are cut so a CPU runs them in seconds.
Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

TINY = {"registry": "qwen2.5-3b", "reference": "decoder", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 128, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "qkv_bias": True,
        "base_dtype": "bfloat16", "lora_rank": 4, "lora_alpha": 8.0,
        "lora_targets": ["q_proj", "k_proj", "v_proj", "o_proj",
                         "gate_proj", "up_proj", "down_proj"]}

CELLS = {
    "qwen2.5-3b.train.silo3": (TINY, dict(batch=4, seq_len=16, eval_batch=4,
                                          min_rows=16, extra_rows=16,
                                          close_sample_every=1)),
    "granite-8b.l8.serve.c16": (dict(TINY, registry="granite-8b",
                                     tie_word_embeddings=False,
                                     qkv_bias=False), {}),
    "granite-8b.l8.serve.c4": (dict(TINY, registry="granite-8b",
                                    tie_word_embeddings=False,
                                    qkv_bias=False), {}),
}


# The served cells are parked (PERF.md, Open questions): their generator,
# mixes, configuration and limits stay under bench/ for a later PR to list
# in BENCHMARK.json again. Until then the tests list them here.
PARKED = {
    "configs": [{"name": "granite-8b.l8",
                 "file": "bench/configs/granite-8b.l8.json"}],
    "workloads": [{"name": "granite-8b.l8.serve.c16",
                   "config": "granite-8b.l8", "traffic": "serve.c16",
                   "chips": 1},
                  {"name": "granite-8b.l8.serve.c4",
                   "config": "granite-8b.l8", "traffic": "serve.c4",
                   "chips": 1}],
}


def tiny_cell(name):
    """The named cell of ``BENCHMARK.json`` (or a parked one) at a size a
    CPU runs quickly."""
    from harness import spec
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for k, entries in PARKED.items():
        bench[k] = bench[k] + entries
    cell = spec.load_cell(name, bench)
    cfg, traffic = CELLS[name]
    cell.config = dict(cfg)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def tiny_run(name, seed=2**33 + 5, seconds=2.0, plant=None):
    """One run of the tiny cell, everything but the chip check."""
    import jax
    from harness import device, runner
    t0 = time.time()
    with device.CompileLog() as log:
        r = runner.Run(cell=tiny_cell(name), seed=seed, seconds=seconds,
                       trace=False, devices=jax.devices(), t_start=t0,
                       compiles=log, plant=plant,
                       peak_table=device.peaks("TPU v5 lite"))
        out = runner.spec.generator(r.cell).run(r)
    return r, out
