"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to ``kv_lora_rank`` latents plus one shared decoupled-RoPE key
per position — the decode cache stores ONLY ``(c_kv, k_rope)`` per token
(512 + 64 dims for the full config vs 128·(128+128) for vanilla GQA: ~57×
smaller).

Training/prefill decompresses K/V and uses the shared blockwise core.
Decode uses *weight absorption* (the TPU-friendly form): queries are mapped
into the latent space through ``w_uk`` so scores are taken directly against the
compressed cache, and attention output is re-expanded through ``w_uv`` — per
step cost is O(S · kv_lora_rank) per head instead of decompressing the cache.

The rope dims follow the published model: rotated as interleaved pairs, and,
under ``rope_scaling``, at YaRN frequencies with the YaRN ``mscale`` on
cos/sin and, squared, on the softmax scale.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import flash_attention
from repro.models.common import (
    Params,
    apply_rope,
    dense,
    make_dense_params,
    make_norm_params,
    apply_norm,
    maybe_lora,
)

NEG_INF = -1e30
# the most one f32 score block of the training attention scan may take: the
# key block shrinks (to 128 at least) until (B, H, Sq, block) fits
SCORE_BLOCK_BYTES = 1 << 29


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def _yarn_correction_dim(rotations: float, dim: int, base: float,
                         max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_find_correction_range(beta_fast: float, beta_slow: float, dim: int,
                               base: float, max_pos: int) -> Tuple[int, int]:
    low = math.floor(_yarn_correction_dim(beta_fast, dim, base, max_pos))
    high = math.ceil(_yarn_correction_dim(beta_slow, dim, base, max_pos))
    return max(low, 0), min(high, dim - 1)


def yarn_frequencies(dim: int, base: float, rs) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (dim//2,) float32, cos/sin mscale) of YaRN: the
    interpolated ``1/(factor·base^(2i/dim))`` below the correction range, the
    original ``1/base^(2i/dim)`` above it, a linear ramp between."""
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = (1.0 / np.float32(base) ** pos).astype(np.float32)
    inter = (1.0 / (np.float32(rs.factor) * np.float32(base) ** pos)
             ).astype(np.float32)
    low, high = yarn_find_correction_range(
        rs.beta_fast, rs.beta_slow, dim, base,
        rs.original_max_position_embeddings)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = (1.0 - ramp).astype(np.float32)
    inv = (inter * (1 - keep) + extra * keep).astype(np.float32)
    m = (yarn_get_mscale(rs.factor, rs.mscale)
         / yarn_get_mscale(rs.factor, rs.mscale_all_dim))
    return inv, m


def softmax_scale(cfg) -> float:
    """``(qk_nope + qk_rope)^-0.5``, times YaRN's ``mscale(factor,
    mscale_all_dim)²`` under YaRN."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_get_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def mla_rope(cfg, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """Rotate the rope dims ``x (..., seq, heads, d)``: de-interleave the
    pairs (the published layout), then rotate halves at plain or YaRN
    frequencies."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if cfg.rope_scaling is None:
        return apply_rope(x, positions, cfg.rope_theta)
    inv, m = yarn_frequencies(x.shape[-1], cfg.rope_theta, cfg.rope_scaling)
    angles = positions[..., None].astype(jnp.float32) * inv
    cos = (jnp.cos(angles) * m)[..., None, :]
    sin = (jnp.sin(angles) * m)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _key_block(b: int, h: int, sq: int, sk: int, block_size: int) -> int:
    bs = min(block_size, sk)
    while bs > 128 and b * h * sq * bs * 4 > SCORE_BLOCK_BYTES:
        bs //= 2
    return bs


def make_mla_params(rng, cfg) -> Params:
    d = cfg.d_model
    h = cfg.num_heads
    qk_nope, qk_rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr, qr = cfg.kv_lora_rank, cfg.q_lora_rank
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(rng, 8)
    return {
        # query path: d → q_lora_rank → heads × (nope + rope)
        "q_down": make_dense_params(ks[0], d, qr, dtype),
        "q_norm": make_norm_params("rmsnorm", qr, dtype),
        "q_up": make_dense_params(ks[1], qr, h * (qk_nope + qk_rope), dtype),
        # kv path: d → kv_lora_rank (+ shared rope key)
        "kv_down": make_dense_params(ks[2], d, kvr + qk_rope, dtype),
        "kv_norm": make_norm_params("rmsnorm", kvr, dtype),
        "k_up": make_dense_params(ks[3], kvr, h * qk_nope, dtype),
        "v_up": make_dense_params(ks[4], kvr, h * dv, dtype),
        "o_proj": make_dense_params(ks[5], h * dv, d, dtype),
    }


def _project_q(cfg, params, x, lora, lora_scale):
    b, s, _ = x.shape
    h = cfg.num_heads
    qk_nope, qk_rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    qd = dense(x, params["q_down"], maybe_lora(lora, "q_down"), lora_scale)
    qd = apply_norm("rmsnorm", params["q_norm"], qd)
    q = dense(qd, params["q_up"], maybe_lora(lora, "q_up"), lora_scale)
    q = q.reshape(b, s, h, qk_nope + qk_rope)
    return q[..., :qk_nope], q[..., qk_nope:]


def _project_kv_latent(cfg, params, x, lora, lora_scale):
    kvr, qk_rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = dense(x, params["kv_down"], maybe_lora(lora, "kv_down"), lora_scale)
    c_kv = apply_norm("rmsnorm", params["kv_norm"], kv[..., :kvr])
    k_rope = kv[..., kvr:]  # (B, S, qk_rope) — ONE shared rope key per position
    return c_kv, k_rope


def init_mla_cache(batch: int, length: int, cfg, dtype=jnp.bfloat16) -> Params:
    return {
        "c_kv": jnp.zeros((batch, length, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, length, cfg.qk_rope_head_dim), dtype),
        "pos": jnp.full((length,), -1, jnp.int32),
    }


def mla_block(
    cfg,
    params: Params,
    x: jnp.ndarray,
    *,
    lora: Optional[Params] = None,
    lora_scale: float = 0.0,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Params] = None,
    decode_position: Optional[jnp.ndarray] = None,
    block_size: int = 1024,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    b, s, _ = x.shape
    h = cfg.num_heads
    qk_nope, qk_rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(s)
    is_decode = decode_position is not None

    q_nope, q_rope = _project_q(cfg, params, x, lora, lora_scale)
    q_pos = decode_position[None] if is_decode else positions
    q_rope = mla_rope(cfg, q_rope, q_pos)

    c_kv, k_rope = _project_kv_latent(cfg, params, x, lora, lora_scale)
    k_rope = mla_rope(cfg, k_rope[..., None, :], q_pos)[..., 0, :]
    scale = softmax_scale(cfg)

    new_cache = cache
    if is_decode:
        # -- absorbed decode against the compressed cache ---------------------
        length = cache["c_kv"].shape[1]
        slot = decode_position % length
        c_kv_c = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), slot, axis=1)
        k_rope_c = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), slot, axis=1)
        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], decode_position[None], slot, axis=0)
        new_cache = {"c_kv": c_kv_c, "k_rope": k_rope_c, "pos": pos}

        w_uk = params["k_up"]["kernel"].reshape(kvr, h, qk_nope)
        w_uv = params["v_up"]["kernel"].reshape(kvr, h, dv)
        # absorb: query → latent space
        q_lat = jnp.einsum("bqhd,chd->bqhc", q_nope, w_uk)  # (B,1,H,kvr)
        s_nope = jnp.einsum("bqhc,bsc->bhqs", q_lat, c_kv_c, preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope_c, preferred_element_type=jnp.float32)
        scores = (s_nope + s_rope) * scale
        valid = (pos >= 0) & (pos <= decode_position)
        scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        ctx_lat = jnp.einsum("bhqs,bsc->bqhc", w.astype(c_kv_c.dtype), c_kv_c)
        out = jnp.einsum("bqhc,chd->bqhd", ctx_lat, w_uv)  # (B,1,H,dv)
    else:
        # -- decompressed training/prefill ------------------------------------
        k_nope = dense(c_kv, params["k_up"], maybe_lora(lora, "k_up"), lora_scale)
        v = dense(c_kv, params["v_up"], maybe_lora(lora, "v_up"), lora_scale)
        k_nope = k_nope.reshape(b, s, h, qk_nope)
        v = v.reshape(b, s, h, dv)
        k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, qk_rope))
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        with jax.named_scope("mla.attn"):
            out = flash_attention(q_full, k_full, v, True, 0, 0,
                                  _key_block(b, h, s, s, block_size), scale)
        if cache is not None:
            length = cache["c_kv"].shape[1]
            ck, kr = c_kv[:, -length:], k_rope[:, -length:]
            ppos = positions[-length:]
            pad = length - ck.shape[1]
            if pad > 0:
                ck = jnp.pad(ck, ((0, 0), (0, pad), (0, 0)))
                kr = jnp.pad(kr, ((0, 0), (0, pad), (0, 0)))
                ppos = jnp.concatenate([ppos, jnp.full((pad,), -1, ppos.dtype)])
            new_cache = {"c_kv": ck.astype(cache["c_kv"].dtype),
                         "k_rope": kr.astype(cache["k_rope"].dtype),
                         "pos": ppos.astype(jnp.int32)}

    out = out.reshape(b, s, h * dv).astype(x.dtype)
    out = dense(out, params["o_proj"], maybe_lora(lora, "o_proj"), lora_scale)
    return out.astype(x.dtype), new_cache
