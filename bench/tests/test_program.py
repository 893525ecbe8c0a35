"""A configuration file reaches the program (``bench/traffic/program.py``):
dense files build what they built before, a latent-attention, routed-expert
file builds its own shapes, a file that claims sizes the program did not
build is refused, and the close comparison reads the expert stacks."""

import dataclasses

import jax
import pytest

import program
from conftest import bench_json, tiny_cell
from harness import device, runner, spec


TINY_MOE = bench_json("tests/data/tiny-mla-moe.json")


@pytest.mark.parametrize("path", ["configs/qwen2.5-3b.json",
                                  "configs/granite-8b.l8.json"])
def test_dense_file_builds_the_same_model_config(path):
    """Field by field what the nine-key mapping of the dense files built."""
    from repro.configs import get_config
    cfg = bench_json(path)
    want = dataclasses.replace(
        get_config(cfg["registry"]), num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], qkv_bias=cfg["qkv_bias"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["base_dtype"],
        head_dim=cfg.get("head_dim", 0))
    got = program.model_config(cfg)
    for f in dataclasses.fields(want):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


def test_mla_moe_file_builds_its_shapes():
    _, wshape, lshape, lcfg = program.seeded_state(TINY_MOE)
    assert lcfg.lora_experts
    want = {
        "attn/q_down/kernel": (64, 48), "attn/q_up/kernel": (48, 96),
        "attn/kv_down/kernel": (64, 40), "attn/k_up/kernel": (32, 64),
        "attn/v_up/kernel": (32, 64), "attn/o_proj/kernel": (64, 64),
        "attn/q_norm/scale": (48,), "attn/kv_norm/scale": (32,),
        "attn_norm/scale": (64,), "mlp_norm/scale": (64,)}
    dense = {"mlp/gate_proj/kernel": (64, 96), "mlp/up_proj/kernel": (64, 96),
             "mlp/down_proj/kernel": (96, 64)}
    moe = {"mlp/router/kernel": (64, 8),
           "mlp/shared/gate_proj/kernel": (64, 32),
           "mlp/shared/up_proj/kernel": (64, 32),
           "mlp/shared/down_proj/kernel": (32, 64),
           "mlp/experts/gate_proj": (8, 64, 32),
           "mlp/experts/up_proj": (8, 64, 32),
           "mlp/experts/down_proj": (8, 32, 64)}
    shapes = {"embed/embedding": (128, 64), "final_norm/scale": (64,),
              "lm_head/kernel": (64, 128)}
    shapes.update({f"dense_layers/{k}": (1,) + v
                   for k, v in dict(want, **dense).items()})
    shapes.update({f"layers/{k}": (2,) + v
                   for k, v in dict(want, **moe).items()})
    assert {k: tuple(v.shape) for k, v in wshape.items()} == shapes
    assert all(v.dtype == jax.numpy.bfloat16 for v in wshape.values())
    # every matrix but the router is adapted, the experts one by one
    adapted = {k[:-2] for k in lshape if k.endswith("/a")}
    assert adapted == {k.removesuffix("/kernel") for k in shapes
                       if k.endswith("kernel") or "/experts/" in k} - {
        "layers/mlp/router", "lm_head"}
    assert lshape["layers/mlp/experts/up_proj/a"].shape == (2, 8, 64, 4)
    assert lshape["layers/mlp/experts/up_proj/b"].shape == (2, 8, 4, 32)


def test_experts_are_adapted_only_under_lora_experts():
    cfg = {k: v for k, v in TINY_MOE.items() if k != "lora_experts"}
    _, _, lshape, lcfg = program.seeded_state(cfg)
    assert not lcfg.lora_experts
    assert not [k for k in lshape if "/experts/" in k]


@pytest.mark.parametrize("change", [
    dict(n_routed_experts=4, published_n_routed_experts=8),
    dict(num_hidden_layers=4, program={"num_layers": 3}),
    dict(lora_targets=["q_proj", "k_proj", "v_proj", "o_proj"]),
], ids=["held_share", "depth", "family_default_targets"])
def test_a_file_claiming_what_the_program_did_not_build_is_refused(change):
    """A share of experts the program cannot hold yet, a size the
    ``program`` object overrides, and targets the program widens (its
    default targets mean the family's) all leave the file's count and the
    program's build apart."""
    with pytest.raises(ValueError):
        program.seeded_state(dict(TINY_MOE, **change))


def test_program_object_sets_fields_and_names_only_fields():
    got = program.model_config(dict(TINY_MOE,
                                    program={"capacity_factor": 2.0}))
    assert got.capacity_factor == 2.0 and got.num_experts == 8
    assert got.dense_d_ff == 96 and got.moe_d_ff == 32
    with pytest.raises(ValueError):
        program.model_config(dict(TINY_MOE, program={"experts_held": 4}))


def test_mla_moe_close_comparison_reads_the_expert_stacks():
    """Two rounds of the tiny MLA + routed-expert trainer through the
    generator's own build and the program's close: the sound close reads
    under the cell's close limit, one that leaves W0 as it was ten times
    over it."""
    cell = tiny_cell("qwen2.5-3b.train.silo3")
    cell.config = dict(TINY_MOE)
    r = runner.Run(cell=cell, seed=2**33 + 21, seconds=0.0, trace=False,
                   devices=jax.devices(), t_start=0.0,
                   compiles=device.CompileLog(),
                   peak_table=device.peaks("TPU v5 lite"))
    drv = spec.generator(cell)
    trainer, _, closer = drv.build(r)
    trainer.run(until=2)
    last = drv.last_close(trainer, closer)
    assert "layers/mlp/experts/down_proj" in last["rows"]
    assert last["after"]["layers/mlp/experts/down_proj"].shape[:2] == (2, 8)
    limit = r.limits()["close_gap"]
    sound = drv.close_readings(cell.config, last)
    assert sound["close_gap"] <= limit, sound
    unchanged = drv.close_readings(cell.config, last, fault="unchanged")
    assert unchanged["close_gap"] >= 10 * limit, unchanged
