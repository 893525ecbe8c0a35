"""Attention: GQA/MHA with RoPE, QKV-bias, sliding-window, blockwise (flash-style)
training path, and ring-buffer / full KV-cache decode paths.

The training/prefill path is *blockwise*: a ``lax.scan`` over KV blocks with an
online-softmax carry — the jnp twin of kernels/flash_swa. Peak activation
memory is O(Sq · block) instead of O(Sq · Sk), which is what lets the 32k
prefill shapes fit the v5e HBM budget in the dry-run.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models.common import (
    Params,
    apply_rope,
    dense,
    make_dense_params,
    maybe_lora,
)

NEG_INF = -1e30


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def make_attention_params(rng, cfg, *, cross: bool = False) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 4)
    dtype = jnp.dtype(cfg.dtype)
    bias = cfg.qkv_bias
    return {
        "q_proj": make_dense_params(ks[0], d, h * hd, dtype, bias=bias),
        "k_proj": make_dense_params(ks[1], d, kv * hd, dtype, bias=bias),
        "v_proj": make_dense_params(ks[2], d, kv * hd, dtype, bias=bias),
        "o_proj": make_dense_params(ks[3], h * hd, d, dtype),
    }


# --------------------------------------------------------------------------
# blockwise core (training / prefill)
# --------------------------------------------------------------------------

def blockwise_attention(
    q: jnp.ndarray,  # (B, Sq, H, Dk)
    k: jnp.ndarray,  # (B, Sk, KV, Dk)
    v: jnp.ndarray,  # (B, Sk, KV, Dv)
    *,
    causal: bool = True,
    window: int = 0,  # 0 → unbounded
    q_offset: int = 0,  # absolute position of q[0] (prefill continuation)
    block_size: int = 1024,
) -> jnp.ndarray:
    """Flash-style attention with online softmax over KV blocks."""
    b, sq, h, dk = q.shape
    _, sk, kvh, dv = v.shape

    # §Perf: GSPMD cannot shard the (kvh, group) split when kvh < model-axis
    # size — it replicates the whole attention computation per model shard.
    # Repeat KV up to full heads (k/v are the SMALL tensors here) and pin the
    # flattened head axis to the model axis. No-op when unconfigured.
    from repro.sharding import act as _act
    if _act.enabled():
        ms = _act.model_size()
        if h % ms == 0 and kvh % ms != 0 and kvh < h:
            rep = h // kvh
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            kvh = h
        q = _act.constrain(q, ("dp", None, "model", None))
        k = _act.constrain(k, ("dp", None, "model", None))
        v = _act.constrain(v, ("dp", None, "model", None))

    group = h // kvh
    scale = dk ** -0.5

    block_size = min(block_size, sk)
    pad = (-sk) % block_size
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblocks = (sk + pad) // block_size

    # (B, Sq, KV, G, Dk) so GQA never materialises repeated KV
    qg = q.reshape(b, sq, kvh, group, dk) * scale
    kb = k.reshape(b, nblocks, block_size, kvh, dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block_size, kvh, dv).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(sq)

    def body(carry, inputs):
        acc, m, l = carry
        blk_idx, kblk, vblk = inputs
        k_pos = blk_idx * block_size + jnp.arange(block_size)
        # scores: (B, KV, G, Sq, Bk)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg, kblk, preferred_element_type=jnp.float32)
        mask = k_pos[None, :] <= q_pos[:, None] if causal else jnp.ones((sq, block_size), bool)
        if window:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        mask = mask & (k_pos < sk)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + p.sum(axis=-1)
        pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * correction[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, kvh, group, sq, dv), jnp.float32)
    m0 = jnp.full((b, kvh, group, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, group, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.arange(nblocks), kb, vb)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (B, KV, G, Sq, Dv) → (B, Sq, H, Dv)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
    return out.astype(v.dtype)


# --------------------------------------------------------------------------
# flash attention with custom VJP (§Perf iteration 2)
#
# jax's AD through the online-softmax scan saves the per-block f32 probability
# tensors for the backward pass — ~2 TB of HBM traffic per train_4k step on
# granite-8b (measured; see EXPERIMENTS.md §Perf). The flash backward
# RECOMPUTES p from (q, k, v, lse) per block instead: residuals shrink to
# out + lse, and the attention boundary cotangent becomes bf16.
# --------------------------------------------------------------------------

def _flash_reshape(q, k, v, scale=None):
    """Shared GQA/model-axis prep: returns (qg*scale, k, v, kvh, group);
    ``scale`` None → ``dk**-0.5``."""
    from repro.sharding import act as _act

    b, sq, h, dk = q.shape
    kvh = k.shape[2]
    if _act.enabled():
        ms = _act.model_size()
        if h % ms == 0 and kvh % ms != 0 and kvh < h:
            rep = h // kvh
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            kvh = h
        q = _act.constrain(q, ("dp", None, "model", None))
        k = _act.constrain(k, ("dp", None, "model", None))
        v = _act.constrain(v, ("dp", None, "model", None))
    group = h // kvh
    scale = dk ** -0.5 if scale is None else scale
    qg = q.reshape(b, sq, kvh, group, dk).astype(jnp.float32) * scale
    return qg, k, v, kvh, group


def _block_mask(sq, block_size, blk_idx, sk, q_offset, causal, window):
    q_pos = q_offset + jnp.arange(sq)
    k_pos = blk_idx * block_size + jnp.arange(block_size)
    mask = k_pos[None, :] <= q_pos[:, None] if causal else jnp.ones(
        (sq, block_size), bool)
    if window:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    return mask & (k_pos < sk)[None, :]


def _flash_fwd_impl(q, k, v, causal, window, q_offset, block_size, scale=None):
    b, sq, h, dk = q.shape
    sk = k.shape[1]
    qg, k, v, kvh, group = _flash_reshape(q, k, v, scale)
    dv_dim = v.shape[-1]
    bs = min(block_size, sk)
    pad = (-sk) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblocks = (sk + pad) // bs
    kb = k.reshape(b, nblocks, bs, kvh, dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, bs, kvh, dv_dim).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        acc, m, l = carry
        blk_idx, kblk, vblk = inputs
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg, kblk,
                       preferred_element_type=jnp.float32)
        mask = _block_mask(sq, bs, blk_idx, sk, q_offset, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, kvh, group, sq, dv_dim), jnp.float32)
    m0 = jnp.full((b, kvh, group, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, group, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (jnp.arange(nblocks), kb, vb))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv_dim)
    lse = m + jnp.log(l)  # (b, kvh, group, sq)
    return out.astype(v.dtype), lse


def _flash_bwd_impl(q, k, v, out, lse, dout, causal, window, q_offset, block_size,
                    scale=None):
    b, sq, h, dk = q.shape
    sk = k.shape[1]
    kvh_orig = k.shape[2]
    qg, k, v, kvh, group = _flash_reshape(q, k, v, scale)
    dv_dim = v.shape[-1]
    bs = min(block_size, sk)
    pad = (-sk) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblocks = (sk + pad) // bs
    kb = k.reshape(b, nblocks, bs, kvh, dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, bs, kvh, dv_dim).transpose(1, 0, 2, 3, 4)

    og = out.reshape(b, sq, kvh, group, dv_dim).transpose(0, 2, 3, 1, 4)
    dog = dout.reshape(b, sq, kvh, group, dv_dim).transpose(0, 2, 3, 1, 4)
    delta = jnp.einsum("bkgqd,bkgqd->bkgq", og.astype(jnp.float32),
                       dog.astype(jnp.float32))  # (b,kvh,g,sq)

    def body(dq_acc, inputs):
        blk_idx, kblk, vblk = inputs
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg, kblk,
                       preferred_element_type=jnp.float32)
        mask = _block_mask(sq, bs, blk_idx, sk, q_offset, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # recomputed probabilities
        dvb = jnp.einsum("bkgqc,bkgqd->bckd", p.astype(dog.dtype), dog,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum("bkgqd,bckd->bkgqc", dog, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])  # (b,kvh,g,sq,c)
        dq_blk = jnp.einsum("bkgqc,bckd->bqkgd", ds.astype(kblk.dtype), kblk,
                            preferred_element_type=jnp.float32)
        dkb = jnp.einsum("bkgqc,bqkgd->bckd", ds.astype(qg.dtype), qg,
                         preferred_element_type=jnp.float32)
        return dq_acc + dq_blk, (dkb, dvb)

    dq0 = jnp.zeros((b, sq, kvh, group, dk), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (jnp.arange(nblocks), kb, vb))
    scale = dk ** -0.5 if scale is None else scale
    dq = (dq * scale).reshape(b, sq, h, dk)
    dk_full = dks.transpose(1, 0, 2, 3, 4).reshape(b, nblocks * bs, kvh, dk)[:, :sk]
    dv_full = dvs.transpose(1, 0, 2, 3, 4).reshape(b, nblocks * bs, kvh, dv_dim)[:, :sk]
    if kvh != kvh_orig:  # GQA repeat in fwd → sum the repeats back
        rep = kvh // kvh_orig
        dk_full = dk_full.reshape(b, sk, kvh_orig, rep, dk).sum(axis=3)
        dv_full = dv_full.reshape(b, sk, kvh_orig, rep, dv_dim).sum(axis=3)
    return (dq.astype(q.dtype), dk_full.astype(q.dtype), dv_full.astype(q.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=0, q_offset=0,
                    block_size=1024, scale=None):
    """Softmax(q·kᵀ · scale) v; ``scale`` None → ``q.shape[-1]**-0.5``."""
    out, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, block_size,
                             scale)
    return out


def _flash_fwd(q, k, v, causal, window, q_offset, block_size, scale):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_offset, block_size,
                               scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_offset, block_size, scale, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_impl(q, k, v, out, lse, dout, causal, window, q_offset,
                           block_size, scale)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# KV cache
# --------------------------------------------------------------------------

def init_kv_cache(batch: int, length: int, kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Params:
    """``length`` is the buffer size: full seq for global attn, window for SWA.

    ``pos`` stores the absolute position held in each slot (-1 = empty) so the
    same code handles both full and ring-buffer caches.
    """
    return {
        "k": jnp.zeros((batch, length, kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, length, kv_heads, head_dim), dtype),
        "pos": jnp.full((length,), -1, jnp.int32),
    }


def cache_write(cache: Params, k_new: jnp.ndarray, v_new: jnp.ndarray,
                position: jnp.ndarray) -> Params:
    """Write one step (Sq=1) at ``position`` (scalar int32); ring if full."""
    length = cache["k"].shape[1]
    slot = position % length
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    pos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], position[None], slot, axis=0)
    return {"k": k, "v": v, "pos": pos}


def decode_attention(q: jnp.ndarray, cache: Params, position: jnp.ndarray,
                     window: int = 0) -> jnp.ndarray:
    """Single-query attention against a (possibly ring) cache.

    q: (B, 1, H, Dk). Returns (B, 1, H, Dv).
    """
    b, _, h, dk = q.shape
    kvh = cache["k"].shape[2]
    group = h // kvh
    scale = dk ** -0.5

    valid = (cache["pos"] >= 0) & (cache["pos"] <= position)
    if window:
        valid = valid & (cache["pos"] > position - window)

    qg = q.reshape(b, kvh, group, dk) * scale
    s = jnp.einsum("bkgd,bckd->bkgc", qg, cache["k"], preferred_element_type=jnp.float32)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckd->bkgd", p.astype(cache["v"].dtype), cache["v"],
                     preferred_element_type=jnp.float32)
    dv = cache["v"].shape[-1]
    return out.reshape(b, 1, h, dv).astype(cache["v"].dtype)


# --------------------------------------------------------------------------
# full attention block (projections + core), self- and cross-attention
# --------------------------------------------------------------------------

def attention_block(
    cfg,
    params: Params,
    x: jnp.ndarray,  # (B, Sq, d_model)
    *,
    lora: Optional[Params] = None,
    lora_scale: float = 0.0,
    positions: Optional[jnp.ndarray] = None,  # (Sq,) absolute positions
    causal: bool = True,
    window: int = 0,
    kv_x: Optional[jnp.ndarray] = None,  # cross-attention source
    cross: Optional[bool] = None,  # force cross-attn (decode reads cache, no kv_x)
    cache: Optional[Params] = None,
    decode_position: Optional[jnp.ndarray] = None,  # scalar → decode mode
    block_size: int = 1024,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Returns (output, updated_cache)."""
    b, sq, _ = x.shape
    hd = cfg.resolved_head_dim
    h, kvh = cfg.num_heads, cfg.num_kv_heads

    q = dense(x, params["q_proj"], maybe_lora(lora, "q_proj"), lora_scale)
    q = q.reshape(b, sq, h, hd)

    if positions is None:
        positions = jnp.arange(sq)

    is_decode = decode_position is not None
    if cross is None:
        cross = kv_x is not None

    if cross and cache is not None and is_decode:
        # cross-attn KV was precomputed at prefill; just read.
        k = v = None
    else:
        src = kv_x if cross else x
        k = dense(src, params["k_proj"], maybe_lora(lora, "k_proj"), lora_scale)
        v = dense(src, params["v_proj"], maybe_lora(lora, "v_proj"), lora_scale)
        sk = src.shape[1]
        k = k.reshape(b, sk, kvh, hd)
        v = v.reshape(b, sk, kvh, hd)

    if cfg.rope and not cross:
        q_positions = decode_position[None] if is_decode else positions
        q = apply_rope(q, q_positions, cfg.rope_theta)
        if k is not None:
            k_positions = decode_position[None] if is_decode else positions
            k = apply_rope(k, k_positions, cfg.rope_theta)

    new_cache = cache
    if is_decode:
        if cross:
            out = decode_attention(q, cache, jnp.array(2**30, jnp.int32), window=0)
        else:
            new_cache = cache_write(cache, k, v, decode_position)
            out = decode_attention(q, new_cache, decode_position, window=window)
    else:
        if cache is not None and not cross:
            # prefill: populate the cache buffer (left-aligned; ring caches get
            # the window-tail; prefill length must fit the buffer here).
            length = cache["k"].shape[1]
            kk, vv = k[:, -length:], v[:, -length:]
            ppos = positions[-length:]
            pad = length - kk.shape[1]
            if pad > 0:
                kk = jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vv = jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0)))
                ppos = jnp.concatenate([ppos, jnp.full((pad,), -1, ppos.dtype)])
            new_cache = {"k": kk.astype(cache["k"].dtype), "v": vv.astype(cache["v"].dtype),
                         "pos": ppos.astype(jnp.int32)}
        elif cache is not None and cross:
            length = cache["k"].shape[1]
            new_cache = {"k": k[:, :length].astype(cache["k"].dtype),
                         "v": v[:, :length].astype(cache["v"].dtype),
                         "pos": jnp.arange(length, dtype=jnp.int32)}
        # named for the train scan's remat policy (transformer._scan_layers)
        out = flash_attention(
            checkpoint_name(q, "q"), checkpoint_name(k, "k"),
            checkpoint_name(v, "v"),
            causal and not cross,
            window,
            0,
            block_size,
        )

    out = checkpoint_name(out.reshape(b, sq, h * hd).astype(x.dtype), "attn_out")
    out = dense(out, params["o_proj"], maybe_lora(lora, "o_proj"), lora_scale)
    return out.astype(x.dtype), new_cache
