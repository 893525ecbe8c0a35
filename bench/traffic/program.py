"""The program's model as a configuration file describes it.

Both generators build the program's ``ModelConfig`` from the file's sizes and
take the weight and adapter trees' structure (never their values) from the
program's own init, by abstract evaluation.

A file reaches the program through :data:`FIELDS`, a table from published
(Hugging Face) keys to ``ModelConfig`` fields, applied to the keys the file
has; a key with no entry stays the reference's. In a file with routed
experts, ``intermediate_size`` is the leading dense layers' width, the
router is ``published_n_routed_experts`` wide (default ``n_routed_experts``)
and ``n_routed_experts`` counts the experts held here. An optional
``"program"`` object sets fields that no published key names.
"""

from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import Any, Dict, List

from harness import flops, weights

FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings", "base_dtype": "dtype",
    "head_dim": "head_dim",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "num_shared_experts",
    "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "first_k_dense",
}


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig: its registry entry with every size the
    configuration file gives taken from the file."""
    from repro.configs import get_config
    base = get_config(cfg["registry"])
    table = dict(FIELDS)
    kw: Dict[str, Any] = {"head_dim": 0}     # absent: hidden / heads
    if cfg.get("n_routed_experts"):
        table["intermediate_size"] = "dense_d_ff"
        kw["num_experts"] = cfg.get("published_n_routed_experts",
                                    cfg["n_routed_experts"])
    kw.update({f: cfg[k] for k, f in table.items() if k in cfg})
    kw = {f: 0 if v is None else v for f, v in kw.items()}   # null ranks
    extra = cfg.get("program", {})
    unknown = sorted(set(extra) - {f.name for f in dataclasses.fields(base)})
    if unknown:
        raise ValueError(f"'program' names no ModelConfig field: {unknown}")
    return dataclasses.replace(base, **dict(kw, **extra))


def seeded_state(cfg: Dict[str, Any]):
    """(model, flat weight shapes, flat adapter shapes, LoRAConfig).

    Refuses a file whose sizes the program did not build: the routed stack's
    expert axis must be the file's ``n_routed_experts``, and the adapted
    matrices must be the ones :func:`harness.flops.adapted` counts."""
    import jax
    from repro.configs import LoRAConfig
    from repro.core.lora import init_lora
    from repro.models import build_model
    mcfg = model_config(cfg)
    model = build_model(mcfg)
    lcfg = LoRAConfig(rank=cfg["lora_rank"], alpha=cfg["lora_alpha"],
                      target_modules=tuple(cfg["lora_targets"]),
                      lora_experts=bool(cfg.get("lora_experts", False)))
    key = jax.random.key(0)
    wshape = jax.eval_shape(model.init, key)
    lshape = jax.eval_shape(lambda p: init_lora(key, p, mcfg, lcfg), wshape)
    wflat, lflat = weights.flatten(wshape), weights.flatten(lshape)
    held = {s.shape[-3] for p, s in wflat.items() if "/experts/" in p}
    if (held or {0}) != {cfg.get("n_routed_experts") or 0}:
        raise ValueError(f"the program's expert stacks hold {held} experts, "
                         f"the file {cfg.get('n_routed_experts')}")
    built = Counter()
    for path in w0_keys(lflat):
        *lead, m, _ = lflat[path + "/a"].shape
        built[(m, lflat[path + "/b"].shape[-1])] += math.prod(lead)
    counted = Counter()
    for mat in flops.adapted(cfg):
        counted[(mat.m, mat.n)] += mat.count
    if built != counted:
        raise ValueError(f"the program adapts {dict(built)}, the file "
                         f"counts {dict(counted)}")
    return model, wflat, lflat, lcfg


def w0_keys(lshape) -> List[str]:
    """The adapted matrices' paths: each adapter's ``<path>/a`` and
    ``<path>/b`` sit beside the base, ``<path>/kernel`` or, for an expert
    stack, the array at ``<path>``."""
    return sorted({p.rsplit("/", 1)[0] for p in lshape})


def w0_path(wshape, key: str) -> str:
    """The flat path of adapted matrix ``key``'s base in ``wshape``."""
    return key if key in wshape else key + "/kernel"


def w0_leaf(params, key: str):
    node = params
    for k in key.split("/"):
        node = node[k]
    return node["kernel"] if isinstance(node, dict) else node
