"""The DeepSeek-V2 cell's counts at its sizes, pinned by hand: the held
experts' grouped GEMMs that ``expert_roofline.train`` divides by, the
training FLOPs a token, and the close's work."""

import pytest

from conftest import bench_json
from harness import flops, spec

CFG = bench_json("configs/deepseek-v2.ep8.json")
TRAFFIC = bench_json("traffic/train.silo3.s4k.json")


def _reader():
    return spec.metric_reader("expert_roofline.train")


def test_held_expert_work_by_hand():
    # 4 expert layers × 20 held experts, each passed by 6 of 160 routed
    # assignments a token: a step of 2 × 4096 tokens gives each of the gate,
    # up and down products 8192 × 6 / 160 × 80 = 24,576 rows
    rows = 24_576
    mn = 5120 * 1536
    lora = 6 * 8 * (5120 + 1536)            # forward, act. and weight grad
    per_row = 3 * (2 * 2 * mn) + 3 * lora   # forward + activation gradient
    steps = 3 * 2                           # 3 silos × 2 local steps a round
    # eval: 3 batches of 1 × 4096 tokens, forward only
    eval_rows = 12_288
    eval_per_row = 3 * (2 * mn) + 3 * (2 * 8 * (5120 + 1536))
    want_flops = steps * rows * per_row + 3 * eval_rows * eval_per_row
    # bytes: each pass reads the 80 × 3 bf16 matrices once and reads and
    # writes its rows (m + n bf16 values a row a product)
    weights = 80 * 3 * mn * 2
    want_bytes = (steps * 2 * (weights + rows * 3 * (5120 + 1536) * 2)
                  + 3 * (weights + eval_rows * 3 * (5120 + 1536) * 2))
    fl, nb = _reader().held_expert_work(CFG, TRAFFIC)
    assert fl == want_flops == 15_808_264_667_136
    assert nb == want_bytes == 69_872_910_336


def test_cell_counts():
    assert flops.train_flops_per_token(CFG, 4096) == 7_580_998_656.0
    assert flops.close_work(CFG, 3, 2) == (192_707_297_280.0,
                                           12_327_952_384.0)
    # 3.145 B parameters: the layers and the untied head and embedding
    n = sum(m.m * m.n * m.count for m in flops.matrices(CFG))
    assert n + 2 * 5120 * 12800 == 3_145_400_320


def test_reader_finds_nothing_without_grouped_gemms():
    class Red:
        ops = {"%fusion.1 = bf16[8,8] fusion": 1.0}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert _reader().read({"reduced": Red(), "peaks": peaks}) is None
    assert _reader().read({"reduced": None, "peaks": peaks}) is None
    Red.ops = {"%ragged-dot-none.3 = bf16[49152,1536] custom-call": 2.0,
               "%fusion.1 = bf16[8,8] fusion": 1.0}
    least = max(15_808_264_667_136 / 197e12, 69_872_910_336 / 819e9)
    assert _reader().read({"reduced": Red(), "peaks": peaks}) == \
        pytest.approx(100 * least / 2.0)


HAND = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000000000 }
    events { metadata_id: 3 offset_ps: 1000000000000 duration_ps: 500000000000 }
    events { metadata_id: 4 offset_ps: 2000000000000 duration_ps: 7000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_step(3)" } }
  event_metadata { key: 2 value { id: 2 name: "%ragged-dot-none.8 = bf16[49152,1536]{1,0:T(8,128)(2,1)} custom-call(%a, %b)" } }
  event_metadata { key: 3 value { id: 3 name: "%ragged-dot-metadata = (s32[21]{0}) custom-call(%c)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.1 = bf16[8,8]{1,0} fusion(%d)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
}
'''


def test_reader_on_a_trace(tmp_path):
    """Through the trace reduction: 1.5 s of grouped-GEMM ops (the GEMM and
    its tile metadata) in a window that also holds a 7 s fusion."""
    from jax.profiler import ProfileData
    from harness import xtrace
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HAND))
    red = xtrace.reduce(str(path), [])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = max(15_808_264_667_136 / 197e12, 69_872_910_336 / 819e9)
    got = _reader().read({"reduced": red, "peaks": peaks})
    assert got == pytest.approx(100 * least / 1.5)
