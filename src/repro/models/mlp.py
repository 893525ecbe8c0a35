"""Feed-forward blocks: gated (SwiGLU/GeGLU) and plain 2-layer MLPs."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models.common import Params, activation, dense, make_dense_params, maybe_lora


def make_mlp_params(rng, cfg, d_ff: int = 0, *, gated: Optional[bool] = None) -> Params:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    gated = (cfg.act == "silu") if gated is None else gated
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 3)
    p = {
        "up_proj": make_dense_params(ks[0], d, ff, dtype, bias=cfg.qkv_bias and cfg.norm == "layernorm"),
        "down_proj": make_dense_params(ks[1], ff, d, dtype, bias=cfg.qkv_bias and cfg.norm == "layernorm"),
    }
    if gated:
        p["gate_proj"] = make_dense_params(ks[2], d, ff, dtype)
    return p


def mlp_block(cfg, params: Params, x: jnp.ndarray, *, lora: Optional[Params] = None,
              lora_scale: float = 0.0) -> jnp.ndarray:
    from repro.sharding import act as _act
    if _act.enabled() and x.ndim >= 2 and x.shape[-2] * (
            x.shape[0] if x.ndim == 3 else 1) <= 4096:
        # decode-scale token counts: replicate the (tiny) tokens so the
        # weight-stationary serving layout (ff sharded over BOTH axes) holds
        # without per-step weight gathers (§Perf it. 7, generalised from MoE).
        x = _act.constrain(x, tuple(None for _ in range(x.ndim)))
    up = checkpoint_name(
        dense(x, params["up_proj"], maybe_lora(lora, "up_proj"), lora_scale), "up")
    if "gate_proj" in params:
        gate = checkpoint_name(
            dense(x, params["gate_proj"], maybe_lora(lora, "gate_proj"), lora_scale),
            "gate")
        h = activation(cfg.act, gate) * up
    else:
        h = activation(cfg.act, up)
    return dense(h, params["down_proj"], maybe_lora(lora, "down_proj"), lora_scale)
