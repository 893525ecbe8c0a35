"""DeepSeek-V2's layer at tiny widths: the program's loss and adapter
gradients match the plain reference (``bench/reference/mla_moe.py``) on
seeded weights, and a configuration file of that shape reaches the program
with its held share, routing and YaRN.

The fixture (``data/tiny-deepseek-v2.json``) keeps the published structure:
YaRN rope on interleaved pairs, group-limited routing over a router 32 wide
(8 groups, top 3, top 6, scaling 16) with group 0's 4 experts held, two
shared experts, a leading dense layer, per-expert adapters."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import program
from conftest import bench_json
from harness import spec, weights

TINY = bench_json("tests/data/tiny-deepseek-v2.json")
RECIPE = bench_json("traffic/train.silo3.json")["recipe"]


def _state(cfg, seed):
    model, wshape, lshape, lcfg = program.seeded_state(cfg)
    w = weights.make_tree(wshape, seed, 1)
    lora = weights.make_tree(lshape, seed, 2)
    return model, w, lora, lcfg


@pytest.mark.parametrize("seed", [3, 2**33 + 7])
def test_program_matches_reference_loss_and_grads(seed):
    """In float32, so that the two differ by the order of sums only."""
    cfg = dict(TINY, base_dtype="float32")
    model, w, lora, lcfg = _state(cfg, seed)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 17))
    batch = {"tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
             "targets": jnp.asarray(tokens[:, 1:], jnp.int32)}
    params = weights.unflatten(w)
    scale = lcfg.scale

    def prog(lo):
        return model.loss(params, dict(batch, loss_mask=jnp.ones((2, 16))),
                          lora=lo, lora_scale=scale)

    (p_loss, metrics), p_grad = jax.value_and_grad(prog, has_aux=True)(
        weights.unflatten(lora))
    ref = spec.reference(cfg["reference"])
    with jax.default_matmul_precision("highest"):
        (r_loss, most), r_grad = jax.value_and_grad(
            lambda lo: ref.loss(cfg, scale, ref.mm_f32, w, lo, batch, 32),
            has_aux=True)(dict(lora))
    assert float(p_loss) == pytest.approx(float(r_loss), rel=1e-5)
    p_flat = weights.flatten(p_grad)
    assert set(p_flat) == set(r_grad)
    for k in sorted(r_grad):
        want = np.asarray(r_grad[k])
        np.testing.assert_allclose(np.asarray(p_flat[k]), want, rtol=2e-3,
                                   atol=2e-3 * np.abs(want).max(), err_msg=k)
    # the program's routing counts: group 0 of 8 holds some of the
    # 32 × 6 assignments, and no more than all of them
    held = int(metrics["moe"]["held_rows"])
    assert 0 < held <= 2 * 32 * 6
    assert int(metrics["moe"]["max_expert_rows"]) == int(most)


def test_reference_capacity_falls_back_to_every_token():
    """A capacity below what routing gives an expert is detected, and the
    result with every token gathered is the same as with ample room."""
    cfg = dict(TINY, base_dtype="float32")
    _, w, lora, lcfg = _state(cfg, 11)
    ref = spec.reference(cfg["reference"])
    rng = np.random.default_rng(11)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 17))
    batch = {"tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
             "targets": jnp.asarray(tokens[:, 1:], jnp.int32)}
    with jax.default_matmul_precision("highest"):
        full, most = ref.loss(cfg, lcfg.scale, ref.mm_f32, w, lora, batch, 32)
        small, _ = ref.loss(cfg, lcfg.scale, ref.mm_f32, w, lora, batch, 1)
    assert int(most) > 1
    assert float(small) != float(full)
    recipe = dict(RECIPE, total_steps=100)
    out = ref.train_steps(cfg, recipe, w, dict(lora), [batch],
                          scale=lcfg.scale)
    assert out["losses"][0] == pytest.approx(float(full), rel=1e-6)


def test_file_reaches_the_program():
    """The file builds the held share, routing and YaRN the program runs;
    the catalog keys that no table maps agree with the registry entry the
    program takes them from."""
    mcfg = program.model_config(TINY)
    assert (mcfg.num_experts, mcfg.local_experts, mcfg.expert_offset) == (
        32, 4, 0)
    assert mcfg.router_aux_loss_coef == 0.0
    assert (mcfg.n_group, mcfg.topk_group, mcfg.topk_method,
            mcfg.norm_topk_prob, mcfg.routed_scaling_factor) == (
        TINY["n_group"], TINY["topk_group"], TINY["topk_method"],
        TINY["norm_topk_prob"], TINY["routed_scaling_factor"])
    assert TINY["scoring_func"] == "softmax"     # the one the router has
    assert dataclasses.asdict(mcfg.rope_scaling) == {
        k: TINY["rope_scaling"][k] for k in TINY["rope_scaling"]}
    assert mcfg.max_position_embeddings == TINY["max_position_embeddings"]
    assert (mcfg.num_layers, mcfg.vocab_size, mcfg.d_model) == (3, 128, 64)
