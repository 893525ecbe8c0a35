"""The benchmark's own yardsticks: peak table and required-work counts."""

import pytest

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
