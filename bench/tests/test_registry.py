"""A configuration, a traffic mix or a per-layer metric is added by new files
plus BENCHMARK.json entries; the harness finds them by name and nothing of
it is edited, for a latent-attention, routed-expert model as for a dense
one."""

import json
import os
import shutil

from conftest import BENCH, ROOT
from harness import spec


def test_new_entries_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # a new configuration, traffic mix and per-layer metric: files only
    cfg = json.load(open(root / "bench/configs/qwen2.5-3b.json"))
    cfg["num_hidden_layers"] = 4
    json.dump(cfg, open(root / "bench/configs/new-model.json", "w"))
    shutil.copy(root / "bench/tests/data/tiny-mla-moe.json",
                root / "bench/configs/new-moe.json")
    json.dump({"generator": "trainer", "clients": 2},
              open(root / "bench/traffic/train.new.json", "w"))
    (root / "bench/metrics/new_metric.train.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["configs"].append({"name": "new-moe", "source": "x",
                             "file": "bench/configs/new-moe.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-model.train.new",
                               "config": "new-model", "traffic": "train.new",
                               "chips": 1, "why": "x"})
    bench["workloads"].append({"name": "new-moe.train.silo3",
                               "config": "new-moe", "traffic": "train.silo3",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric.train", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "local step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["new-model.train.new"]})
    bench["end_to_end"][1]["workloads"] += ["new-model.train.new",
                                            "new-moe.train.silo3"]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("new-model.train.new", bench_dir=str(root / "bench"))
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "train_tokens_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric.train"]
    reader = spec.metric_reader("new_metric.train", str(root / "bench"))
    assert reader.read({}) == 42.0
    assert spec.generator(cell, str(root / "bench")).__name__.endswith("trainer")
    # the MLA + routed-expert cell loads, builds and is counted
    moe = spec.load_cell("new-moe.train.silo3", bench_dir=str(root / "bench"))
    gen = spec.generator(moe, str(root / "bench"))
    _, _, lshape, _ = gen.seeded_state(moe.config)
    assert "layers/mlp/experts/up_proj/a" in lshape
    assert gen.flops.train_flops_per_token(moe.config, 256) > 0
    assert all(x > 0 for x in gen.flops.close_work(moe.config, 3, 2))
    assert [m["name"] for m in moe.end_to_end] == ["setup_s",
                                                   "train_tokens_per_s"]
    # the cells already there are unchanged
    old = spec.load_cell("qwen2.5-3b.train.silo3", bench_dir=str(root / "bench"))
    assert "new_metric.train" not in [m["name"] for m in old.per_layer]


def test_every_cell_reports_setup_another_metric_and_a_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        names = [m["name"] for m in cell.end_to_end]
        assert names[0] == "setup_s" and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           w["name"] + ".json"))
