"""The program's model as a configuration file describes it.

Both generators build the program's ``ModelConfig`` from the file's sizes and
take the weight and adapter trees' structure (never their values) from the
program's own init, by abstract evaluation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

from harness import weights


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig: its registry entry with every size taken
    from the configuration file."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    return dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], qkv_bias=cfg["qkv_bias"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["base_dtype"],
        head_dim=cfg.get("head_dim", 0))


def seeded_state(cfg: Dict[str, Any]):
    """(model, flat weight shapes, flat adapter shapes, LoRAConfig)."""
    import jax
    from repro.configs import LoRAConfig
    from repro.core.lora import init_lora
    from repro.models import build_model
    mcfg = model_config(cfg)
    model = build_model(mcfg)
    lcfg = LoRAConfig(rank=cfg["lora_rank"], alpha=cfg["lora_alpha"],
                      target_modules=tuple(cfg["lora_targets"]))
    key = jax.random.key(0)
    wshape = jax.eval_shape(model.init, key)
    lshape = jax.eval_shape(lambda p: init_lora(key, p, mcfg, lcfg), wshape)
    return model, weights.flatten(wshape), weights.flatten(lshape), lcfg


def w0_keys(lshape) -> List[str]:
    """The adapted matrices' paths: each adapter's ``<path>/a`` and
    ``<path>/b`` sit beside the base's ``<path>/kernel``."""
    return sorted({p.rsplit("/", 1)[0] for p in lshape})


def w0_leaf(params, key: str):
    node = params
    for k in key.split("/"):
        node = node[k]
    return node["kernel"]
