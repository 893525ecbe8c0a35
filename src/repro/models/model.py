"""Unified model API: ``build_model(cfg) → Model`` for every family.

``Model`` is a thin namespace of pure functions closed over the config:

* ``init(rng) → params``
* ``apply(params, batch, lora=…, lanes=1) → (logits, aux)`` — training
  forward; ``lanes`` counts copies vmapped side by side on one device (the
  train scan's memory reckoning, transformer.TrainMemory)
* ``loss(params, batch, lora=…, lanes=1) → (scalar, metrics)``; a model
  with routed-expert layers adds ``metrics["moe"]`` (``held_rows``,
  ``max_expert_rows``: transformer.forward's routing counts)
* ``init_cache(batch_size, cache_len) → cache``
* ``prefill(params, batch, cache, lora=…) → (logits, cache)``
* ``decode_step(params, tokens, cache, position, lora=…) → (logits, cache)``

``batch``: ``tokens``/``targets``/``loss_mask`` (B,S) plus family extras —
``frames`` (encdec stub frontend) / ``vision_embeds`` (vlm stub frontend).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.common import cross_entropy


Batch = Dict[str, jnp.ndarray]


@dataclass(frozen=True)
class Model:
    cfg: Any
    init: Callable
    apply: Callable
    loss: Callable
    init_cache: Callable
    prefill: Callable
    decode_step: Callable


def build_model(cfg, *, moe_impl: str = "ragged", block_size: int = 1024) -> Model:
    fam = cfg.family

    if fam == "encdec":
        def init(rng):
            return encdec.make_params(rng, cfg)

        def train_forward(params, batch, lora=None, lora_scale=0.0, lanes=1):
            enc_out = encdec.encode(cfg, params, batch["frames"], lora=lora,
                                    lora_scale=lora_scale, remat=True,
                                    block_size=block_size)
            logits, _ = encdec.decoder_forward(
                cfg, params, batch["tokens"], enc_out, lora=lora,
                lora_scale=lora_scale, mode="train", block_size=block_size)
            return logits, jnp.zeros((), jnp.float32), {}

        def init_cache(batch_size, cache_len, dtype=jnp.bfloat16):
            return encdec.init_cache(cfg, batch_size, cache_len, dtype)

        def prefill(params, batch, cache, lora=None, lora_scale=0.0):
            enc_out = encdec.encode(cfg, params, batch["frames"], lora=lora,
                                    lora_scale=lora_scale, block_size=block_size)
            logits, cache = encdec.decoder_forward(
                cfg, params, batch["tokens"], enc_out, lora=lora,
                lora_scale=lora_scale, mode="prefill", cache=cache,
                block_size=block_size)
            return logits, cache

        def decode_step(params, tokens, cache, position, lora=None, lora_scale=0.0):
            logits, cache = encdec.decoder_forward(
                cfg, params, tokens, None, lora=lora, lora_scale=lora_scale,
                mode="decode", cache=cache, position=position,
                block_size=block_size)
            return logits, cache

    else:
        def init(rng):
            return transformer.make_params(rng, cfg)

        def train_forward(params, batch, lora=None, lora_scale=0.0, lanes=1):
            logits, aux, _, stats = transformer.forward(
                cfg, params, batch["tokens"], lora=lora, lora_scale=lora_scale,
                mode="train", extra_embeds=batch.get("vision_embeds"),
                moe_impl=moe_impl, block_size=block_size, lanes=lanes)
            return logits, aux, stats

        def init_cache(batch_size, cache_len, dtype=jnp.bfloat16):
            return transformer.init_cache(cfg, batch_size, cache_len, dtype)

        def prefill(params, batch, cache, lora=None, lora_scale=0.0):
            logits, _, cache, _ = transformer.forward(
                cfg, params, batch["tokens"], lora=lora, lora_scale=lora_scale,
                mode="prefill", cache=cache,
                extra_embeds=batch.get("vision_embeds"),
                moe_impl=moe_impl, block_size=block_size)
            return logits, cache

        def decode_step(params, tokens, cache, position, lora=None, lora_scale=0.0):
            logits, _, cache, _ = transformer.forward(
                cfg, params, tokens, lora=lora, lora_scale=lora_scale,
                mode="decode", cache=cache, position=position,
                moe_impl=moe_impl, block_size=block_size)
            return logits, cache

    def apply(params, batch, lora=None, lora_scale=0.0, lanes=1):
        return train_forward(params, batch, lora, lora_scale, lanes)[:2]

    def loss(params, batch, lora=None, lora_scale=0.0, lanes=1):
        logits, aux, stats = train_forward(params, batch, lora=lora,
                                           lora_scale=lora_scale, lanes=lanes)
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if fam == "vlm" and "vision_embeds" in batch:
            # logits cover [vision prefix | text]; score text positions only.
            vt = batch["vision_embeds"].shape[1]
            logits = logits[:, vt:]
        ce, metrics = cross_entropy(logits, targets, mask)
        total = ce + aux
        metrics = dict(metrics)
        metrics["aux_loss"] = aux
        metrics["total_loss"] = total
        if stats:
            metrics["moe"] = stats
        return total, metrics

    return Model(cfg=cfg, init=init, apply=apply, loss=loss,
                 init_cache=init_cache, prefill=prefill, decode_step=decode_step)
