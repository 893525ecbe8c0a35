"""Operations and bytes the algorithm requires, from shapes alone.

The yardstick of ``mfu.*`` and ``*_roofline``: what the work needs, not what
a compiler emitted. Nothing here depends on how the program implements it
(remat, chunking, kernels or jnp), so a faster implementation moves the
measured time and never the count.

Shapes come from a configuration file (Hugging Face key names).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def head_dim(cfg: Dict[str, Any]) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


def projections(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of every projection of one decoder layer."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q_proj": (d, h * hd), "k_proj": (d, kv * hd),
            "v_proj": (d, kv * hd), "o_proj": (h * hd, d),
            "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}


def adapted(cfg: Dict[str, Any]) -> List[Tuple[int, int]]:
    """(m, n) of every adapted matrix, one entry per layer and target."""
    proj = projections(cfg)
    per_layer = [proj[t] for t in cfg["lora_targets"]]
    return per_layer * int(cfg["num_hidden_layers"])


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Required FLOPs of one LoRA training token on a frozen base.

    Forward 2·N_mm; backward 2·N_mm for activation gradients only (the base
    is frozen, so no W0 weight gradient), less the input gradient of the
    first layer's q/k/v, which nothing needs; the adapters 6·N_lora; causal
    attention's score and value products, forward and backward (3x the
    forward), over the mean causal context (S+1)/2. N_mm counts matmul
    parameters: the output head yes (tied or not), the embedding gather no.
    Recomputation is not counted.
    """
    proj = projections(cfg)
    layers = int(cfg["num_hidden_layers"])
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    n_mm = layers * sum(m * n for m, n in proj.values()) + d * vocab
    r = int(cfg["lora_rank"])
    n_lora = sum(r * (m + n) for m, n in adapted(cfg))
    qkv_in = sum(m * n for k, (m, n) in proj.items()
                 if k in ("q_proj", "k_proj", "v_proj"))
    hd, h = head_dim(cfg), cfg["num_attention_heads"]
    attn_fwd = layers * 4 * h * hd * (seq_len + 1) / 2
    return (2 * n_mm + 2 * n_mm - 2 * qkv_in + 6 * n_lora + 3 * attn_fwd)


def close_work(cfg: Dict[str, Any], clients: int,
               w0_bytes: int) -> Tuple[float, float]:
    """(FLOPs, bytes) a FedEx round close requires: read and write every
    adapted W0 once (``w0_bytes`` per element), read the clients' f32
    factors, write the f32 global adapter, and form Σ_c w_c a_c b_c − ā b̄
    (2·m·n·r·(C+1) FLOPs per matrix). Independent of chunking and backend.
    """
    r = int(cfg["lora_rank"])
    flops = 0.0
    nbytes = 0.0
    for m, n in adapted(cfg):
        flops += 2.0 * m * n * r * (clients + 1)
        nbytes += 2.0 * m * n * w0_bytes + 4.0 * r * (m + n) * (clients + 1)
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
