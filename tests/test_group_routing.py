"""DeepSeek-V2's ``group_limited_greedy`` router against a plain numpy
routing, and the default greedy router left as it was.

The published gate: f32 softmax scores; each group's score is its best
expert's; the top ``topk_group`` groups are kept, every other expert's
score is set to 0; the top-k of what is left are the chosen experts, their
weights multiplied by ``routed_scaling_factor`` and not renormalised. Ties
go to the lower index, groups and experts alike.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe


def _numpy_route(logits, n_group, topk_group, k, scaling):
    x = logits.astype(np.float32)
    e = np.exp(x - x.max(-1, keepdims=True))
    scores = e / e.sum(-1, keepdims=True)
    t, n = scores.shape
    grouped = scores.reshape(t, n_group, n // n_group)
    best = grouped.max(-1)
    idx, w = [], []
    for i in range(t):
        groups = np.argsort(-best[i], kind="stable")[:topk_group]
        masked = np.zeros_like(grouped[i])
        masked[groups] = grouped[i][groups]
        masked = masked.reshape(n)
        top = np.argsort(-masked, kind="stable")[:k]
        idx.append(top)
        w.append(masked[top] * scaling)
    return np.array(idx), np.array(w)


def _cfg(n, groups, topk_group, k):
    return dataclasses.replace(
        get_config("deepseek-v2-236b").reduced(), dtype="float32",
        num_experts=n, num_experts_per_tok=k, n_group=groups,
        topk_group=topk_group, router_aux_loss_coef=0.0)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("ties", [False, True])
def test_group_limited_greedy_matches_numpy(seed, ties):
    n, groups, topk_group, k = 160, 8, 3, 6
    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 2, (64, n)).astype(np.float32)
    if ties:
        # coarse logits: many equal scores inside and across groups
        logits = np.round(logits).astype(np.float32)
        logits[:8] = 0.0                      # every expert tied
    cfg = _cfg(n, groups, topk_group, k)
    assert cfg.topk_method == "group_limited_greedy"
    assert not cfg.norm_topk_prob and cfg.routed_scaling_factor == 16.0
    # identity router: the logits are the rows of x
    w, idx, aux = moe.router_topk(cfg, {"kernel": jnp.eye(n)},
                                  jnp.asarray(logits))
    want_idx, want_w = _numpy_route(logits, groups, topk_group, k, 16.0)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5, atol=1e-7)
    assert float(aux) == 0.0
    # every chosen expert lies in one of the token's top groups
    for row in want_idx:
        assert len(set(row // (n // groups))) <= topk_group


def test_greedy_default_is_unchanged():
    """The default router (greedy, renormalised, one group) computes what
    it computed before the grouped gate existed."""
    cfg = dataclasses.replace(get_config("mixtral-8x22b").reduced(),
                              dtype="float32")
    assert (cfg.topk_method, cfg.n_group, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("greedy", 1, True, 1.0)
    x = jax.random.normal(jax.random.key(0), (32, cfg.d_model))
    kernel = 0.1 * jax.random.normal(jax.random.key(1),
                                     (cfg.d_model, cfg.num_experts))
    w, idx, aux = moe.router_topk(cfg, {"kernel": kernel}, x)
    probs = jax.nn.softmax(jnp.matmul(x, kernel).astype(jnp.float32), -1)
    tw, ti = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    tw = tw / jnp.maximum(tw.sum(-1, keepdims=True), 1e-9)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ti))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(tw))
    assert float(aux) > 0.0
