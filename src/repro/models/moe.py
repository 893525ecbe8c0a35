"""Mixture-of-Experts: top-k router + grouped-GEMM dispatch.

Two execution paths share one set of parameters:

* ``ragged`` (default): sort-by-expert + ``jax.lax.ragged_dot`` grouped GEMM —
  FLOP-proportional (the megablocks pattern, TPU-native via ragged_dot). Expert
  weights carry the expert axis, sharded over the ``model`` mesh axis for
  expert parallelism.
* ``dense``: every expert on every token via einsum — the oracle used by tests
  and by tiny smoke configs (O(E/k) FLOP overhead, trivially shardable).

Routing is greedy top-k over the router's softmax (renormalised), or
DeepSeek-V2's ``group_limited_greedy``: the top ``topk_group`` of ``n_group``
expert groups by their best score, then top-k inside them, weights scaled by
``routed_scaling_factor`` and not renormalised. Under expert parallelism a
layer holds ``cfg.local_experts`` of the router's ``num_experts`` (from
``expert_offset``): it routes over all of them and computes only what its
own experts give the tokens routed to them; assignments to other experts
sort past the last group, so nothing is dropped and no capacity is set.

Router aux load-balance loss follows Switch/Mixtral: ``E · Σ_e f_e · p_e``.
Optional per-expert LoRA (cfg flag ``lora_experts``) applies stacked rank-r
factors through the same grouped GEMMs — the FedEx-LoRA residual machinery in
core/ then applies per expert, unchanged.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Params, activation, make_dense_params, normal_init
from repro.models.mlp import make_mlp_params, mlp_block


def make_moe_params(rng, cfg) -> Params:
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    e = cfg.held_experts
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "router": make_dense_params(ks[0], d, cfg.num_experts, dtype),
        "experts": {
            "up_proj": normal_init(ks[1], (e, d, ff), dtype),
            "gate_proj": normal_init(ks[2], (e, d, ff), dtype),
            "down_proj": normal_init(ks[3], (e, ff, d), dtype),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = make_mlp_params(ks[4], cfg, d_ff=ff * cfg.num_shared_experts, gated=True)
    return p


def router_topk(cfg, router_params: Params, x: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (topk_weights (T,k), topk_idx (T,k), aux_loss scalar)."""
    if cfg.topk_method == "group_limited_greedy":
        # the published gate: f32 logits and scores
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_params["kernel"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_w, topk_idx = jax.lax.top_k(group_limited(cfg, probs),
                                         cfg.num_experts_per_tok)
    else:
        logits = jnp.matmul(x, router_params["kernel"]).astype(jnp.float32)  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_w, topk_idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    else:
        topk_w = topk_w * cfg.routed_scaling_factor

    if not cfg.router_aux_loss_coef:
        return topk_w, topk_idx, jnp.zeros((), jnp.float32)
    # load-balance aux loss: E * Σ_e (fraction routed to e) * (mean prob of e)
    e = cfg.num_experts
    one_hot = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32).sum(axis=1)  # (T, E)
    f = one_hot.mean(axis=0) / cfg.num_experts_per_tok
    pbar = probs.mean(axis=0)
    aux = e * jnp.sum(f * pbar) * cfg.router_aux_loss_coef
    return topk_w, topk_idx, aux


def group_limited(cfg, probs: jnp.ndarray) -> jnp.ndarray:
    """``probs`` (T, E) with every expert outside the token's top
    ``topk_group`` groups (ranked by their best expert) set to 0."""
    t, e = probs.shape
    grouped = probs.reshape(t, cfg.n_group, e // cfg.n_group)
    _, top_groups = jax.lax.top_k(grouped.max(axis=-1), cfg.topk_group)
    keep = jnp.zeros((t, cfg.n_group), bool).at[
        jnp.arange(t)[:, None], top_groups].set(True)
    return jnp.where(keep[..., None], grouped, 0.0).reshape(t, e)


def _expert_ffn_dense(cfg, experts: Params, x: jnp.ndarray, w_full: jnp.ndarray,
                      lora: Optional[Params], lora_scale: float) -> jnp.ndarray:
    """(T, d) × routing weights (T, E) → (T, d).

    Every expert on every token, combine fused into the down projection so the
    (T, E, d) intermediate never materialises and the expert axis reduces
    straight into the all-reduce (the GSPMD-friendly form — §Perf it. 5).
    """
    up = jnp.einsum("td,edf->tef", x, experts["up_proj"])
    gate = jnp.einsum("td,edf->tef", x, experts["gate_proj"])
    if lora is not None and "experts" in lora:
        le = lora["experts"]
        up = up + lora_scale * jnp.einsum(
            "ter,erf->tef", jnp.einsum("td,edr->ter", x, le["up_proj"]["a"].astype(x.dtype)),
            le["up_proj"]["b"].astype(x.dtype))
        gate = gate + lora_scale * jnp.einsum(
            "ter,erf->tef", jnp.einsum("td,edr->ter", x, le["gate_proj"]["a"].astype(x.dtype)),
            le["gate_proj"]["b"].astype(x.dtype))
    h = activation(cfg.act, gate) * up
    hw = h * w_full[..., None].astype(h.dtype)  # routing-weighted (T, E, ff)
    y = jnp.einsum("tef,efd->td", hw, experts["down_proj"])
    if lora is not None and "experts" in lora:
        le = lora["experts"]
        y = y + lora_scale * jnp.einsum(
            "ter,erd->td", jnp.einsum("tef,efr->ter", hw, le["down_proj"]["a"].astype(x.dtype)),
            le["down_proj"]["b"].astype(x.dtype))
    return y


def _ragged(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def _expert_ffn_ragged(cfg, experts: Params, x_sorted: jnp.ndarray,
                       group_sizes: jnp.ndarray,
                       lora: Optional[Params], lora_scale: float) -> jnp.ndarray:
    """Grouped GEMM over tokens sorted by expert id."""
    up = _ragged(x_sorted, experts["up_proj"], group_sizes)
    gate = _ragged(x_sorted, experts["gate_proj"], group_sizes)
    if lora is not None and "experts" in lora:
        le = lora["experts"]
        up = up + lora_scale * _ragged(
            _ragged(x_sorted, le["up_proj"]["a"].astype(x_sorted.dtype), group_sizes),
            le["up_proj"]["b"].astype(x_sorted.dtype), group_sizes)
        gate = gate + lora_scale * _ragged(
            _ragged(x_sorted, le["gate_proj"]["a"].astype(x_sorted.dtype), group_sizes),
            le["gate_proj"]["b"].astype(x_sorted.dtype), group_sizes)
    h = activation(cfg.act, gate) * up
    y = _ragged(h, experts["down_proj"], group_sizes)
    if lora is not None and "experts" in lora:
        le = lora["experts"]
        y = y + lora_scale * _ragged(
            _ragged(h, le["down_proj"]["a"].astype(h.dtype), group_sizes),
            le["down_proj"]["b"].astype(h.dtype), group_sizes)
    return y


def moe_block(cfg, params: Params, x: jnp.ndarray, *, lora: Optional[Params] = None,
              lora_scale: float = 0.0, impl: str = "ragged"
              ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) → (y, aux_loss, stats).

    ``stats``: ``held_rows``, the assignments to the experts held here, and
    ``max_expert_rows``, the most any one of them got (int32 scalars)."""
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    k = cfg.num_experts_per_tok
    e = cfg.held_experts
    partial = e < cfg.num_experts

    with jax.named_scope("moe.route"):
        topk_w, topk_idx, aux = router_topk(cfg, params["router"], xf)
        if partial:
            # held experts keep their local id; every other one becomes e,
            # past the last group
            local = topk_idx - cfg.expert_offset
            topk_idx = jnp.where((local >= 0) & (local < e), local, e)
        rows = jnp.bincount(topk_idx.reshape(t * k), length=e + 1)[:e]
    stats = {"held_rows": rows.sum().astype(jnp.int32),
             "max_expert_rows": rows.max().astype(jnp.int32)}

    with jax.named_scope("moe.experts"):
        if impl == "dense":
            w_full = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32) * topk_w[..., None]  # (T,k,E)
            w_full = w_full.sum(axis=1)  # (T, E)
            from repro.sharding import act as _act
            if _act.enabled() and t <= 4096:
                # decode-scale token counts: replicating the (tiny) tokens lets
                # the weight-stationary serving layout hold — otherwise GSPMD
                # gathers expert weights over the batch axis every step (§Perf 7).
                xf = _act.constrain(xf, (None, None))
                w_full = _act.constrain(w_full, (None, None))
            y = _expert_ffn_dense(cfg, params["experts"], xf, w_full, lora, lora_scale)
        elif impl == "ragged":
            flat_expert = topk_idx.reshape(t * k)  # (T*k,)
            sort_idx = jnp.argsort(flat_expert)
            # token index each sorted row came from
            token_idx = sort_idx // k
            x_sorted = jnp.take(xf, token_idx, axis=0)  # (T*k, d)
            if partial:
                # rows past the last group belong to experts held elsewhere:
                # the grouped GEMMs leave them undefined on some backends, so
                # they are zeroed going in and coming out, which also zeroes
                # their cotangents in the backward
                held = (jnp.take(flat_expert, sort_idx) < e)[:, None]
                x_sorted = jnp.where(held, x_sorted, 0)
            y_sorted = _expert_ffn_ragged(cfg, params["experts"], x_sorted, rows,
                                          lora, lora_scale)
            if partial:
                y_sorted = jnp.where(held, y_sorted, 0)
            w_sorted = jnp.take(topk_w.reshape(t * k), sort_idx)
            y_weighted = y_sorted * w_sorted[:, None].astype(y_sorted.dtype)
            # combine: scatter-add back onto tokens
            y = jnp.zeros((t, d), y_sorted.dtype).at[token_idx].add(y_weighted)
        else:
            raise ValueError(f"unknown moe impl {impl!r}")

    if "shared" in params:
        y = y + mlp_block(cfg, params["shared"], xf,
                          lora=(lora or {}).get("shared"), lora_scale=lora_scale)

    return y.reshape(b, s, d), aux, stats
