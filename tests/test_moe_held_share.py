"""An expert layer that holds a share of the experts (expert parallelism).

With 8 routed experts in 4 groups, the four layers that each hold one group
of 2 route over all 8 and compute only their own experts' part; those parts,
with the shared expert counted once, add up to the uncut layer. Assignments
to experts held elsewhere drop no token and add nothing.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe

SHARES = 4


def _layer(seed=0):
    cfg = dataclasses.replace(
        get_config("deepseek-v2-236b").reduced(), dtype="float32",
        num_experts=8, num_experts_per_tok=3, n_group=4, topk_group=2,
        num_shared_experts=1, router_aux_loss_coef=0.0)
    ks = jax.random.split(jax.random.key(seed), 4)
    params = moe.make_moe_params(ks[0], cfg)
    # per-expert adapters on the routed experts, non-zero in both factors
    ff, d, r = cfg.moe_d_ff, cfg.d_model, 4
    lora = {"experts": {
        name: {"a": 0.1 * jax.random.normal(k, (8, m, r)),
               "b": 0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                            (8, r, n))}
        for name, (m, n), k in zip(("gate_proj", "up_proj", "down_proj"),
                                   ((d, ff), (d, ff), (ff, d)),
                                   jax.random.split(ks[1], 3))}}
    x = jax.random.normal(ks[2], (2, 16, d))
    return cfg, params, lora, x


def _share(cfg, params, lora, i):
    held = cfg.num_experts // SHARES
    sl = slice(i * held, (i + 1) * held)
    p = dict(params, experts={k: v[sl] for k, v in params["experts"].items()})
    lo = {"experts": {k: {f: v[sl] for f, v in ab.items()}
                      for k, ab in lora["experts"].items()}}
    return dataclasses.replace(cfg, local_experts=held,
                               expert_offset=i * held), p, lo


@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_held_shares_add_up_to_the_whole_layer(impl):
    cfg, params, lora, x = _layer()
    whole, _, whole_stats = moe.moe_block(cfg, params, x, lora=lora,
                                          lora_scale=2.0, impl=impl)
    shared = moe.mlp_block(cfg, params["shared"], x.reshape(-1, x.shape[-1])
                           ).reshape(x.shape)
    total = jnp.zeros_like(x)
    held_rows = 0
    for i in range(SHARES):
        c, p, lo = _share(cfg, params, lora, i)
        assert p["experts"]["up_proj"].shape[0] == 2
        assert p["router"]["kernel"].shape[-1] == 8      # routes over all
        y, _, stats = moe.moe_block(c, p, x, lora=lo, lora_scale=2.0,
                                    impl=impl)
        total = total + y - shared
        held_rows += int(stats["held_rows"])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # every assignment is computed by exactly one share: no token dropped
    assert held_rows == int(whole_stats["held_rows"]) == 2 * 16 * 3


def test_ragged_and_dense_agree_on_a_share():
    cfg, params, lora, x = _layer(seed=3)
    c, p, lo = _share(cfg, params, lora, 2)
    yr, _, sr = moe.moe_block(c, p, x, lora=lo, lora_scale=2.0, impl="ragged")
    yd, _, sd = moe.moe_block(c, p, x, lora=lo, lora_scale=2.0, impl="dense")
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yd), rtol=1e-5,
                               atol=1e-5)
    assert int(sr["max_expert_rows"]) == int(sd["max_expert_rows"])
    assert 0 < int(sr["max_expert_rows"]) <= int(sr["held_rows"])


def test_share_gradients_are_the_whole_layers():
    """Under a loss linear in the layer's output, the gradient of a held
    share's adapters is the whole layer's gradient for those experts."""
    cfg, params, lora, x = _layer(seed=5)
    c_out = jax.random.normal(jax.random.key(9), x.shape)

    def loss(c, p, lo):
        y, _, _ = moe.moe_block(c, p, x, lora=lo, lora_scale=2.0)
        return jnp.sum(y * c_out)

    g_whole = jax.grad(lambda lo: loss(cfg, params, lo))(lora)
    for i in range(SHARES):
        c, p, lo = _share(cfg, params, lora, i)
        g = jax.grad(lambda lo_: loss(c, p, lo_))(lo)
        for name in ("gate_proj", "up_proj", "down_proj"):
            for f in ("a", "b"):
                want = np.asarray(g_whole["experts"][name][f])[2 * i:2 * i + 2]
                np.testing.assert_allclose(np.asarray(g["experts"][name][f]),
                                           want, rtol=1e-4,
                                           atol=1e-5 * np.abs(want).max())


def _poisoned_ragged(lhs, rhs, group_sizes):
    """A grouped GEMM that leaves the rows past the last group NaN, in its
    output and in its input's cotangent: what a backend may leave there."""
    rows = jnp.arange(lhs.shape[0])[:, None] < group_sizes.sum()

    @jax.custom_vjp
    def rd(a, b):
        return jnp.where(rows, jax.lax.ragged_dot(a, b, group_sizes), jnp.nan)

    def fwd(a, b):
        return rd(a, b), (a, b)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                         *res)
        da, db = vjp(jnp.where(rows, g, 0))
        return jnp.where(rows, da, jnp.nan), db

    rd.defvjp(fwd, bwd)
    return rd(lhs, rhs.astype(lhs.dtype))


def test_rows_past_the_last_group_do_not_leak(monkeypatch):
    """Whatever the grouped GEMM leaves in the rows of experts held
    elsewhere, the share's output and every gradient (adapters, the layer's
    input, the router) stay finite and equal to the clean ones."""
    cfg, params, lora, x = _layer(seed=7)
    c, p, lo = _share(cfg, params, lora, 1)
    c_out = jax.random.normal(jax.random.key(4), x.shape)

    def loss(p_, lo_, x_):
        y, _, _ = moe.moe_block(c, p_, x_, lora=lo_, lora_scale=2.0)
        return jnp.sum(y * c_out)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    clean = grad(p, lo, x)
    monkeypatch.setattr(moe, "_ragged", _poisoned_ragged)
    poisoned = grad(p, lo, x)
    for want, got in zip(jax.tree.leaves(clean), jax.tree.leaves(poisoned)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def test_held_share_config_is_checked():
    cfg = get_config("deepseek-v2-236b")
    assert cfg.held_experts == 160
    held = dataclasses.replace(cfg, local_experts=20, expert_offset=140)
    assert held.held_experts == 20
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, local_experts=20, expert_offset=150)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, n_group=7)
    smoke = cfg.reduced()
    assert smoke.num_experts == 4 and 4 % smoke.n_group == 0
    assert 1 <= smoke.topk_group <= smoke.n_group
