"""The benchmark's own yardsticks: peak table and required-work counts."""

import pytest

from conftest import bench_json
from harness import device, flops

# paper-tiny-like shapes (repro.configs.paper_models: d=64, 2 layers)
CFG = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "vocab_size": 100, "lora_rank": 4,
       "lora_targets": ["q_proj", "k_proj", "v_proj", "o_proj",
                        "gate_proj", "up_proj", "down_proj"]}


def test_peaks_of_v5e():
    pk = device.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_train_flops_by_hand():
    # head_dim 16; q,o 64x64; k,v 64x32; gate,up 64x128; down 128x64
    per_layer = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128     # 36,864
    n_mm = 2 * per_layer + 64 * 100                           # 80,128
    n_lora = 2 * 4 * ((64 + 64) * 2 + (64 + 32) * 2 + (64 + 128) * 3)
    qkv_in = 64 * 64 + 2 * 64 * 32                            # 8,192
    attn = 2 * 4 * 4 * 16 * (8 + 1) / 2                       # fwd, S=8
    want = 4 * n_mm - 2 * qkv_in + 6 * n_lora + 3 * attn
    assert flops.train_flops_per_token(CFG, 8) == want


def test_close_work_by_hand():
    fl, nb = flops.close_work(CFG, clients=3, w0_bytes=2)
    mats = [(64, 64), (64, 32), (64, 32), (64, 64),
            (64, 128), (64, 128), (128, 64)] * 2
    assert fl == sum(2 * m * n * 4 * 4 for m, n in mats)
    assert nb == sum(2 * m * n * 2 + 4 * 4 * (m + n) * 4 for m, n in mats)


TINY_MOE = bench_json("tests/data/tiny-mla-moe.json")


@pytest.mark.parametrize("path,seq,clients,want", [
    ("configs/qwen2.5-3b.json", 256, 3,
     (12_535_791_616.0, 177_570_054_144.0, 11_337_596_928.0)),
    ("configs/granite-8b.l8.json", 256, 16,
     (7_816_282_112.0, 474_593_886_208.0, 7_335_837_696.0)),
], ids=["qwen2.5-3b", "granite-8b.l8"])
def test_dense_files_count_as_before(path, seq, clients, want):
    """The counts of every dense configuration, pinned to the floats the
    GQA-only yardstick gave before MLA and routed experts were counted."""
    cfg = bench_json(path)
    assert flops.train_flops_per_token(cfg, seq) == want[0]
    assert flops.close_work(cfg, clients, 2) == want[1:]


# tests/data/tiny-mla-moe.json with 4 of its 8 routed experts held here
HELD = dict(TINY_MOE, n_routed_experts=4, published_n_routed_experts=8)
# d 64, 4 heads; q 48 -> 4 x (16 nope + 8 rope), kv 32 (+8 rope), v 16
ATTN = {"q_down": (64, 48), "q_up": (48, 96), "kv_down": (64, 40),
        "k_up": (32, 64), "v_up": (32, 64), "o_proj": (64, 64)}
DENSE_FF = [(64, 96), (64, 96), (96, 64)]
EXPERT = [(64, 32), (64, 32), (32, 64)]     # one shared expert is as wide


def test_mla_moe_train_flops_by_hand():
    # 3 layers: 1 dense, 2 with a router of 8, 1 shared and 4 held experts,
    # each held expert passed by 2 of 8 tokens
    attn = sum(m * n for m, n in ATTN.values())               # 18,432
    moe = 64 * 8 + sum(m * n for m, n in EXPERT) * (1 + 4 * 2 / 8)
    n_mm = 3 * attn + sum(m * n for m, n in DENSE_FF) + 2 * moe + 64 * 128
    lora = lambda mats: sum(4 * (m + n) for m, n in mats)    # noqa: E731
    n_lora = (3 * lora(ATTN.values()) + lora(DENSE_FF)
              + 2 * lora(EXPERT) * (1 + 4 * 2 / 8))
    first_in = 64 * 48 + 64 * 40                              # q_down, kv_down
    attn_fwd = 3 * 2 * 4 * (24 + 16) * (8 + 1) / 2
    want = 4 * n_mm - 2 * first_in + 6 * n_lora + 3 * attn_fwd
    assert n_mm == 107_520 and want == 519_904
    assert flops.train_flops_per_token(HELD, 8) == want


def test_mla_moe_close_work_by_hand():
    """Every adapted W0 is folded once, each held expert's included."""
    mats = (list(ATTN.values()) * 3 + DENSE_FF + EXPERT * 2
            + EXPERT * 2 * 4)
    fl, nb = flops.close_work(HELD, clients=3, w0_bytes=2)
    assert fl == sum(2 * m * n * 4 * 4 for m, n in mats)
    assert nb == sum(2 * m * n * 2 + 4 * 4 * (m + n) * 4 for m, n in mats)


def test_mla_without_query_compression_by_hand():
    """``q_lora_rank`` null: one q_proj, which reads the layer's input."""
    cfg = {k: v for k, v in TINY_MOE.items()
           if k not in ("n_routed_experts", "first_k_dense_replace")}
    cfg.update(q_lora_rank=None, num_hidden_layers=2,
               lora_targets=["q_proj", "kv_down", "o_proj"])
    attn = {"q_proj": (64, 96), "kv_down": (64, 40), "k_up": (32, 64),
            "v_up": (32, 64), "o_proj": (64, 64)}
    n_mm = 2 * (sum(m * n for m, n in attn.values())
                + sum(m * n for m, n in DENSE_FF)) + 64 * 128
    adapted = [attn["q_proj"], attn["kv_down"], attn["o_proj"]] * 2
    n_lora = sum(4 * (m + n) for m, n in adapted)
    want = (4 * n_mm - 2 * (64 * 96 + 64 * 40) + 6 * n_lora
            + 3 * 2 * 2 * 4 * (24 + 16) * (8 + 1) / 2)
    assert flops.train_flops_per_token(cfg, 8) == want
    fl, _ = flops.close_work(cfg, clients=3, w0_bytes=2)
    assert fl == sum(2 * m * n * 4 * 4 for m, n in adapted)


def test_close_work_ignores_chunking_and_backend():
    """The served mixes differ in close_chunk (and hence in the programs
    that run); the work the close requires is the same function of the
    configuration and the client count for both."""
    import inspect
    assert set(inspect.signature(flops.close_work).parameters) == {
        "cfg", "clients", "w0_bytes"}
    assert flops.close_work(CFG, 4, 2) == flops.close_work(dict(CFG), 4, 2)


def test_min_seconds_takes_the_larger_bound():
    pk = device.peaks("TPU v5 lite")
    assert flops.min_seconds(197e12, 0.0, pk) == pytest.approx(1.0)
    assert flops.min_seconds(0.0, 819e9, pk) == pytest.approx(1.0)
    assert flops.min_seconds(197e12, 2 * 819e9, pk) == pytest.approx(2.0)
