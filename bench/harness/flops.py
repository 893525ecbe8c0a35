"""Operations and bytes the algorithm requires, from shapes alone.

The yardstick of ``mfu.*`` and ``*_roofline``: what the work needs, not what
a compiler emitted. Nothing here depends on how the program implements it
(remat, chunking, kernels or jnp), so a faster implementation moves the
measured time and never the count.

Shapes come from a configuration file (Hugging Face key names): GQA
attention, or latent attention (MLA) where ``kv_lora_rank`` is given; a
dense FFN, or ``first_k_dense_replace`` dense layers of ``intermediate_size``
followed by routed-expert layers where ``n_routed_experts`` is given.

Routing is data, so the count does not follow it: a held routed expert is
taken to see k / E of the tokens (``num_experts_per_tok`` over the router's
published width, ``published_n_routed_experts``, default
``n_routed_experts``), the expected share under a uniform router, counted
from shapes alone. Every other matrix sees every token.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

# the matrices that read a layer's input; the first layer's input gradient
# through them is needed by nothing
INPUT_PROJ = ("q_proj", "k_proj", "v_proj", "q_down", "kv_down")


@dataclass(frozen=True)
class Matrix:
    """``count`` instances (layers × experts held) of one (m, n) matrix,
    each passed by ``share`` of the tokens."""
    name: str
    m: int
    n: int
    count: int
    share: float = 1.0
    expert: bool = False      # a routed expert's: adapted under lora_experts


def head_dims(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(query/key, value) width of one attention head."""
    if cfg.get("kv_lora_rank"):
        return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    hd = int(cfg.get("head_dim") or
             cfg["hidden_size"] // cfg["num_attention_heads"])
    return hd, hd


def attention(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of every projection of one layer's attention, named as
    the program names them."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if cfg.get("kv_lora_rank"):
        kvr, qr = cfg["kv_lora_rank"], cfg.get("q_lora_rank")
        nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        q = h * (nope + rope)
        return dict({"q_down": (d, qr), "q_up": (qr, q)} if qr
                    else {"q_proj": (d, q)},
                    kv_down=(d, kvr + rope), k_up=(kvr, h * nope),
                    v_up=(kvr, h * dv), o_proj=(h * dv, d))
    hd, kv = head_dims(cfg)[0], cfg["num_key_value_heads"]
    return {"q_proj": (d, h * hd), "k_proj": (d, kv * hd),
            "v_proj": (d, kv * hd), "o_proj": (h * hd, d)}


def ffn(d: int, ff: int) -> Dict[str, Tuple[int, int]]:
    return {"gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}


def matrices(cfg: Dict[str, Any]) -> List[Matrix]:
    """Every matrix of the decoder layers (the output head aside)."""
    d, layers = cfg["hidden_size"], int(cfg["num_hidden_layers"])
    attn = attention(cfg)
    held = int(cfg.get("n_routed_experts") or 0)
    if not held:
        mats = dict(attn, **ffn(d, cfg["intermediate_size"]))
        return [Matrix(k, m, n, layers) for k, (m, n) in mats.items()]
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("expert layers other than every layer after the "
                         "leading dense ones are not counted")
    dense = int(cfg.get("first_k_dense_replace") or 0)
    moe = layers - dense
    ff = cfg["moe_intermediate_size"]
    routed = int(cfg.get("published_n_routed_experts") or held)
    share = cfg["num_experts_per_tok"] / routed
    out = [Matrix(k, m, n, layers) for k, (m, n) in attn.items()]
    out += [Matrix(k, m, n, dense)
            for k, (m, n) in ffn(d, cfg["intermediate_size"]).items()]
    out.append(Matrix("router", d, routed, moe))
    shared = int(cfg.get("n_shared_experts") or 0)
    if shared:
        out += [Matrix(k, m, n, moe)
                for k, (m, n) in ffn(d, shared * ff).items()]
    out += [Matrix(k, m, n, moe * held, share, expert=True)
            for k, (m, n) in ffn(d, ff).items()]
    return [mat for mat in out if mat.count]


def adapted(cfg: Dict[str, Any]) -> List[Matrix]:
    """The adapted matrices: those named in ``lora_targets``, and the routed
    experts' where ``lora_experts`` is set."""
    targets, experts = cfg["lora_targets"], bool(cfg.get("lora_experts"))
    return [mat for mat in matrices(cfg)
            if (experts if mat.expert else mat.name in targets)]


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Required FLOPs of one LoRA training token on a frozen base.

    Forward 2·N_mm; backward 2·N_mm for activation gradients only (the base
    is frozen, so no W0 weight gradient), less the input gradient of the
    first layer's projections that read its input (q/k/v; MLA's q_down or
    q_proj and kv_down), which nothing needs; the adapters 6·N_lora; causal
    attention's score and value products, forward 2·h·(d_qk + d_v) over the
    mean causal context (S+1)/2, backward twice that. N_mm counts matmul
    parameters, each weighted by the share of tokens through it: the output
    head yes (tied or not), the embedding gather no. Recomputation is not
    counted.
    """
    layers = int(cfg["num_hidden_layers"])
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    n_mm = sum(mat.m * mat.n * mat.count * mat.share
               for mat in matrices(cfg)) + d * vocab
    r = int(cfg["lora_rank"])
    n_lora = sum(r * (mat.m + mat.n) * mat.count * mat.share
                 for mat in adapted(cfg))
    first_in = sum(m * n for k, (m, n) in attention(cfg).items()
                   if k in INPUT_PROJ)
    d_qk, d_v = head_dims(cfg)
    h = cfg["num_attention_heads"]
    attn_fwd = layers * 2 * h * (d_qk + d_v) * (seq_len + 1) / 2
    return (2 * n_mm + 2 * n_mm - 2 * first_in + 6 * n_lora + 3 * attn_fwd)


def close_work(cfg: Dict[str, Any], clients: int,
               w0_bytes: int) -> Tuple[float, float]:
    """(FLOPs, bytes) a FedEx round close requires: read and write every
    adapted W0 once (``w0_bytes`` per element), each held expert's
    included, read the clients' f32 factors, write the f32 global adapter,
    and form Σ_c w_c a_c b_c − ā b̄ (2·m·n·r·(C+1) FLOPs per matrix). The
    close folds every expert, routed to or not. Independent of chunking
    and backend.
    """
    r = int(cfg["lora_rank"])
    flops = 0.0
    nbytes = 0.0
    for mat in adapted(cfg):
        m, n = mat.m, mat.n
        flops += mat.count * 2.0 * m * n * r * (clients + 1)
        nbytes += mat.count * (2.0 * m * n * w0_bytes
                               + 4.0 * r * (m + n) * (clients + 1))
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
