"""Decoder-only stacks for the dense / moe / ssm / hybrid / vlm families.

Layer stacks are ``jax.lax.scan`` over stacked parameters so the HLO is O(1)
in depth (critical for CPU-hosted dry-run compiles of 60–81-layer configs).
Heterogeneous architectures scan over their *period*:

* gemma3: period = 5 local (sliding-window) layers + 1 global layer
* zamba2: period = ``attn_every`` mamba2 layers + one application of the single
  parameter-SHARED attention+MLP block (+ trailing mamba layers)
* xlstm:  period = (slstm_every − 1) mLSTM blocks + 1 sLSTM block
* deepseek-v2: ``first_k_dense`` dense-FFN MLA layers, then MLA+MoE layers

Modes: ``train`` (no cache), ``prefill`` (fills caches), ``decode`` (single
token against caches). MoE aux losses accumulate through the scan. In train
mode every scanned layer body runs under ``jax.checkpoint``: when the device
reports a memory limit and the named matmul outputs the backward reads
(:data:`SAVED_NAMES`) fit beside the parameters and the f32 logits, the scan
keeps them and the backward recomputes only norms and rank-r products;
otherwise (or on a device with no limit: the CPU, host-side compiles) nothing
is kept and the backward re-runs each layer's forward.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.extend import core as jax_core

from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import attention_block, init_kv_cache, make_attention_params
from repro.models.common import (
    Params,
    apply_norm,
    embed,
    make_dense_params,
    make_embedding_params,
    make_norm_params,
    normal_init,
    unembed,
)
from repro.models.mlp import make_mlp_params, mlp_block


# ==========================================================================
# init helpers
# ==========================================================================

def stacked_init(rng, n: int, fn):
    """vmap a per-layer init over n split rngs → params stacked on axis 0."""
    rngs = jax.random.split(rng, n)
    return jax.vmap(fn)(rngs)


def _dense_layer_init(cfg, use_moe: bool, d_ff_override: int = 0):
    def init(rng):
        ks = jax.random.split(rng, 2)
        p = {
            "attn_norm": make_norm_params(cfg.norm, cfg.d_model, jnp.dtype(cfg.dtype)),
            "mlp_norm": make_norm_params(cfg.norm, cfg.d_model, jnp.dtype(cfg.dtype)),
        }
        if cfg.mla:
            p["attn"] = mla_mod.make_mla_params(ks[0], cfg)
        else:
            p["attn"] = make_attention_params(ks[0], cfg)
        if use_moe:
            p["mlp"] = moe_mod.make_moe_params(ks[1], cfg)
        else:
            p["mlp"] = make_mlp_params(ks[1], cfg, d_ff=d_ff_override or cfg.d_ff)
        return p

    return init


def _mamba_layer_init(cfg):
    def init(rng):
        return {
            "norm": make_norm_params(cfg.norm, cfg.d_model, jnp.dtype(cfg.dtype)),
            "mamba": ssm_mod.make_mamba2_params(rng, cfg),
        }

    return init


# ==========================================================================
# layer bodies
# ==========================================================================

def _attn_mlp_layer(cfg, p, x, *, lora, lora_scale, positions, window, cache,
                    decode_position, moe_impl, block_size):
    """Standard pre-norm transformer layer; returns (x, new_cache, aux,
    stats): ``stats`` is the expert layer's routing counts (moe.moe_block),
    None for a dense FFN."""
    h_in = apply_norm(cfg.norm, p["attn_norm"], x)
    if cfg.mla:
        h, new_cache = mla_mod.mla_block(
            cfg, p["attn"], h_in, lora=(lora or {}).get("attn"),
            lora_scale=lora_scale, positions=positions, cache=cache,
            decode_position=decode_position, block_size=block_size)
    else:
        h, new_cache = attention_block(
            cfg, p["attn"], h_in, lora=(lora or {}).get("attn"),
            lora_scale=lora_scale, positions=positions, window=window,
            cache=cache, decode_position=decode_position, block_size=block_size)
    x = checkpoint_name(x + h, "resid_mid")
    m_in = apply_norm(cfg.norm, p["mlp_norm"], x)
    aux = jnp.zeros((), jnp.float32)
    stats = None
    if "router" in p["mlp"]:
        m, aux, stats = moe_mod.moe_block(cfg, p["mlp"], m_in,
                                          lora=(lora or {}).get("mlp"),
                                          lora_scale=lora_scale, impl=moe_impl)
    else:
        m = mlp_block(cfg, p["mlp"], m_in, lora=(lora or {}).get("mlp"),
                      lora_scale=lora_scale)
    return x + m, new_cache, aux, stats


def _mamba_layer(cfg, p, x, *, lora, lora_scale, cache, decode):
    h_in = apply_norm(cfg.norm, p["norm"], x)
    h, new_cache = ssm_mod.mamba2_block(cfg, p["mamba"], h_in,
                                        lora=(lora or {}).get("mamba"),
                                        lora_scale=lora_scale, cache=cache,
                                        decode=decode)
    return x + h, new_cache


# ==========================================================================
# scan runner
# ==========================================================================

# The layer's matmul outputs its backward reads, as checkpoint_name tags them:
# q/k/v as flash attention receives them and its output before o_proj
# (attention.attention_block), the residual after attention
# (_attn_mlp_layer), gate and up (mlp.mlp_block). down_proj's output is read
# by nothing in the backward, so it is not kept.
SAVED_NAMES = ("q", "k", "v", "attn_out", "resid_mid", "gate", "up")
# Share of the device memory left beside the fixed bytes that the kept
# residuals may take; the rest is for the backward's temporaries and the
# state held between programs (optimizer, ring buffers).
HEADROOM_SHARE = 0.75


def residual_bytes_kept(saved: int, fixed: int, limit: Optional[int]) -> int:
    """Bytes of named residuals a train scan keeps: ``saved`` when it fits in
    :data:`HEADROOM_SHARE` of what ``limit`` leaves beside ``fixed`` (bytes
    already spoken for), else 0 — full remat. No ``limit`` → 0."""
    if not limit or saved <= 0:
        return 0
    return saved if saved <= HEADROOM_SHARE * (limit - fixed) else 0


@functools.lru_cache(maxsize=None)
def device_bytes_limit() -> Optional[int]:
    """The default device's ``memory_stats()["bytes_limit"]``, or None where
    the device reports none (the CPU). A constant of the process, so a call
    compiles the same program every time."""
    stats = jax.devices()[0].memory_stats()
    return (stats or {}).get("bytes_limit")


class TrainMemory:
    """What a train forward's remat'd scans may keep: the device ``limit``,
    the bytes already spoken for (``fixed``: parameters, f32 logits and their
    gradient, residuals earlier stacks kept) and the ``lanes`` of this forward
    that run side by side on one device (vmapped clients)."""

    def __init__(self, limit: Optional[int], fixed: int, lanes: int = 1):
        self.limit, self.fixed, self.lanes = limit, fixed, lanes

    def checkpoint(self, body, x, xs):
        """``jax.checkpoint(body)`` saving :data:`SAVED_NAMES` when the scan's
        named residuals (one layer's × layers × lanes) fit; else exactly
        ``jax.checkpoint(body)``."""
        if not self.limit:
            return jax.checkpoint(body)
        one = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)
        layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), xs)
        n = jax.tree_util.tree_leaves(xs)[0].shape[0]
        saved = named_bytes(jax.make_jaxpr(body)(one, layer).jaxpr) * n * self.lanes
        kept = residual_bytes_kept(saved, self.fixed, self.limit)
        if not kept:
            return jax.checkpoint(body)
        self.fixed += kept
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(*SAVED_NAMES))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(j, jax_core.ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, jax_core.Jaxpr):
                yield j


def named_bytes(jaxpr) -> int:
    """Bytes of the values ``jaxpr`` tags with :data:`SAVED_NAMES` (inner
    scans count once per iteration)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name" and eqn.params["name"] in SAVED_NAMES:
            total += sum(v.aval.size * v.aval.dtype.itemsize for v in eqn.outvars)
        reps = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        total += reps * sum(named_bytes(j) for j in _sub_jaxprs(eqn))
    return total


def saved_residual_bytes(jaxpr) -> int:
    """Bytes the remat'd scans of a traced train forward keep for the
    backward: the named values under every ``jax.checkpoint`` with a policy,
    times the scan lengths around it. 0 under full remat."""
    total = 0
    for eqn in jaxpr.eqns:
        reps = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        if eqn.params.get("policy") is not None:
            total += reps * sum(named_bytes(j) for j in _sub_jaxprs(eqn))
        else:
            total += reps * sum(saved_residual_bytes(j) for j in _sub_jaxprs(eqn))
    return total


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def _scan_layers(body, x, xs, *, remat):
    """scan ``body(x, xs_slice) → (x, ys_slice)`` over the leading layer axis.

    ``remat`` is False (no checkpoint) or the forward's :class:`TrainMemory`,
    which picks what the checkpointed body keeps."""
    fn = remat.checkpoint(body, x, xs) if remat else body

    def wrapped(carry, inp):
        return fn(carry, inp)

    return jax.lax.scan(wrapped, x, xs)


def _maybe(tree, default_like):
    """Replace a None subtree with a scan-compatible zeros dummy."""
    return tree if tree is not None else default_like


# ==========================================================================
# parameter construction per family
# ==========================================================================

def make_params(rng, cfg) -> Params:
    ks = jax.random.split(rng, 8)
    dtype = jnp.dtype(cfg.dtype)
    params: Params = {"embed": make_embedding_params(ks[0], cfg.vocab_size, cfg.d_model, dtype)}
    if cfg.learned_pos_embeddings:
        params["pos_embed"] = make_embedding_params(
            ks[1], cfg.max_position_embeddings, cfg.d_model, dtype)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        if cfg.local_global_ratio:
            period = cfg.local_global_ratio + 1
            nper = cfg.num_layers // period
            params["periods"] = {
                "local": stacked_init(
                    ks[2], nper,
                    lambda r: stacked_init(r, cfg.local_global_ratio,
                                           _dense_layer_init(cfg, False))),
                "global": stacked_init(ks[3], nper, _dense_layer_init(cfg, False)),
            }
        else:
            params["layers"] = stacked_init(ks[2], cfg.num_layers,
                                            _dense_layer_init(cfg, False))
    elif fam == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        if cfg.first_k_dense:
            params["dense_layers"] = stacked_init(
                ks[2], cfg.first_k_dense,
                _dense_layer_init(cfg, False, d_ff_override=cfg.dense_d_ff))
        params["layers"] = stacked_init(ks[3], n_moe, _dense_layer_init(cfg, True))
    elif fam == "hybrid":
        nper = cfg.num_layers // cfg.attn_every
        trailing = cfg.num_layers - nper * cfg.attn_every
        params["mamba_layers"] = stacked_init(
            ks[2], nper,
            lambda r: stacked_init(r, cfg.attn_every, _mamba_layer_init(cfg)))
        if trailing:
            params["mamba_trailing"] = stacked_init(ks[4], trailing, _mamba_layer_init(cfg))
        # zamba2: ONE parameter-shared attention+MLP block
        params["shared_attn"] = _dense_layer_init(cfg, False)(ks[3])
    elif fam == "ssm":  # xlstm
        period = cfg.slstm_every
        nper = cfg.num_layers // period
        params["periods"] = {
            "mlstm": stacked_init(
                ks[2], nper,
                lambda r: stacked_init(r, period - 1,
                                       lambda r2: xlstm_mod.make_mlstm_params(r2, cfg))),
            "slstm": stacked_init(ks[3], nper,
                                  lambda r: xlstm_mod.make_slstm_params(r, cfg)),
        }
    else:
        raise ValueError(f"make_params: unsupported family {fam!r} (encdec has its own)")

    params["final_norm"] = make_norm_params(cfg.norm, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = make_dense_params(ks[5], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.family == "vlm":
        # projector stub: identity-sized projection applied to provided patch
        # embeddings (the ViT itself is stubbed per the assignment).
        params["vision_proj"] = make_dense_params(ks[6], cfg.d_model, cfg.d_model, dtype)
    return params


# ==========================================================================
# caches
# ==========================================================================

def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16) -> Params:
    """Cache pytree mirroring the stack layout.

    ``cache_len`` is the max absolute sequence length; windowed layers allocate
    ring buffers of ``min(window, cache_len)``.
    """
    hd = cfg.resolved_head_dim
    kvh = cfg.num_kv_heads

    def attn_cache(n: Optional[int], window: int):
        length = min(window, cache_len) if window else cache_len
        if cfg.mla:
            one = lambda: mla_mod.init_mla_cache(batch, length, cfg, dtype)
        else:
            one = lambda: init_kv_cache(batch, length, kvh, hd, dtype)
        if n is None:
            return one()
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[one() for _ in range(n)])

    fam = cfg.family
    if fam in ("dense", "vlm"):
        if cfg.local_global_ratio:
            period = cfg.local_global_ratio + 1
            nper = cfg.num_layers // period
            local = attn_cache(None, cfg.local_window)
            local = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (nper, cfg.local_global_ratio) + x.shape), local)
            glob = attn_cache(nper, 0)
            return {"local": local, "global": glob}
        return {"layers": attn_cache(cfg.num_layers, cfg.sliding_window)}
    if fam == "moe":
        out = {"layers": attn_cache(cfg.num_layers - cfg.first_k_dense, cfg.sliding_window)}
        if cfg.first_k_dense:
            out["dense_layers"] = attn_cache(cfg.first_k_dense, cfg.sliding_window)
        return out
    if fam == "hybrid":
        nper = cfg.num_layers // cfg.attn_every
        trailing = cfg.num_layers - nper * cfg.attn_every
        mamba_one = ssm_mod.init_mamba_cache(batch, cfg, dtype)
        mamba = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (nper, cfg.attn_every) + x.shape), mamba_one)
        out = {"mamba": mamba, "shared_attn": attn_cache(nper, 0)}
        if trailing:
            out["mamba_trailing"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (trailing,) + x.shape), mamba_one)
        return out
    if fam == "ssm":
        period = cfg.slstm_every
        nper = cfg.num_layers // period
        m_one = xlstm_mod.init_mlstm_cache(batch, cfg, dtype)
        s_one = xlstm_mod.init_slstm_cache(batch, cfg, dtype)
        return {
            "mlstm": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (nper, period - 1) + x.shape), m_one),
            "slstm": jax.tree.map(lambda x: jnp.broadcast_to(x, (nper,) + x.shape), s_one),
        }
    raise ValueError(f"init_cache: unsupported family {fam!r}")


# ==========================================================================
# forward
# ==========================================================================

def forward(
    cfg,
    params: Params,
    tokens: jnp.ndarray,  # (B, S) int32
    *,
    lora: Optional[Params] = None,
    lora_scale: float = 0.0,
    mode: str = "train",  # train | prefill | decode
    cache: Optional[Params] = None,
    position: Optional[jnp.ndarray] = None,  # scalar decode position
    extra_embeds: Optional[jnp.ndarray] = None,  # vlm: (B, Vt, d) patch embeds
    moe_impl: str = "ragged",
    block_size: int = 1024,
    lanes: int = 1,  # train: copies of this forward vmapped on one device
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Params], Dict[str, jnp.ndarray]]:
    """Returns (logits (B,S,V) f32, aux_loss scalar, new_cache, stats):
    ``stats`` is empty but for routed-expert stacks, where it holds this
    forward's ``held_rows`` (assignments to the experts held here, over all
    expert layers) and ``max_expert_rows`` (the most any one expert of one
    layer got)."""
    b, s = tokens.shape
    decode = mode == "decode"
    x = embed(params["embed"], tokens)

    offset = 0
    if cfg.family == "vlm" and extra_embeds is not None and not decode:
        from repro.models.common import dense as dense_fn
        vis = dense_fn(extra_embeds.astype(x.dtype), params["vision_proj"])
        x = jnp.concatenate([vis, x], axis=1)
        s = x.shape[1]

    remat = False
    if mode == "train":
        # spoken for before any residual: the parameters, and per lane the
        # adapters and the f32 logits with their gradient
        logits_bytes = 2 * b * s * cfg.vocab_size * 4
        remat = TrainMemory(device_bytes_limit(),
                            _tree_bytes(params) + lanes * (_tree_bytes(lora) + logits_bytes),
                            lanes)

    if decode:
        positions = None
        dpos = position.astype(jnp.int32)
    else:
        positions = jnp.arange(s)
        dpos = None

    if cfg.learned_pos_embeddings:
        if decode:
            pe = jnp.take(params["pos_embed"]["embedding"],
                          jnp.minimum(dpos, cfg.max_position_embeddings - 1), axis=0)
            x = x + pe[None, None, :]
        else:
            pe = params["pos_embed"]["embedding"][:s]
            x = x + pe[None]

    aux_total = jnp.zeros((), jnp.float32)
    new_cache = None
    stats: Dict[str, jnp.ndarray] = {}

    lora = lora or {}
    fam = cfg.family

    def attn_body_factory(window):
        def body(carry, inp):
            xc, aux = carry
            p, lo, ca = inp
            xo, nc, a, st = _attn_mlp_layer(
                cfg, p, xc, lora=lo, lora_scale=lora_scale, positions=positions,
                window=window, cache=ca, decode_position=dpos, moe_impl=moe_impl,
                block_size=block_size)
            return (xo, aux + a), (nc, st)
        return body

    def run_stack(x, layer_params, layer_lora, layer_cache, window):
        n = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        lo = layer_lora if layer_lora is not None else _broadcast_none(n)
        if layer_cache is None:
            def body_nc(carry, inp):
                p, l = inp
                (xo, aux), (_, st) = attn_body_factory(window)(carry, (p, l, None))
                return (xo, aux), st
            (x, aux), st = _scan_layers(body_nc, (x, jnp.zeros((), jnp.float32)),
                                        (layer_params, lo), remat=remat)
            return x, aux, None, st
        (x, aux), (ncache, st) = _scan_layers(
            attn_body_factory(window), (x, jnp.zeros((), jnp.float32)),
            (layer_params, lo, layer_cache), remat=False)
        return x, aux, ncache, st

    if fam in ("dense", "vlm") and not cfg.local_global_ratio:
        x, aux, nc, _ = run_stack(x, params["layers"], lora.get("layers"),
                               None if cache is None else cache["layers"],
                               cfg.sliding_window)
        aux_total += aux
        new_cache = None if cache is None else {"layers": nc}

    elif fam in ("dense", "vlm"):
        # gemma3: scan over periods of (5 local + 1 global)
        def period_body(carry, inp):
            xc, aux = carry
            pp, lo, ca = inp
            local_ca = None if ca is None else ca["local"]
            # inner scan over the local layers of this period
            def local_body(c2, inp2):
                p2, l2, ca2 = inp2
                x2, a2 = c2
                xo, nc2, a, _ = _attn_mlp_layer(
                    cfg, p2, x2, lora=l2, lora_scale=lora_scale,
                    positions=positions, window=cfg.local_window, cache=ca2,
                    decode_position=dpos, moe_impl=moe_impl, block_size=block_size)
                return (xo, a2 + a), nc2
            nlocal = cfg.local_global_ratio
            lo_local = lo["local"] if lo is not None else _broadcast_none(nlocal)
            if local_ca is None:
                def lb(c2, inp2):
                    p2, l2 = inp2
                    (xo, a2), _ = local_body(c2, (p2, l2, None))
                    return (xo, a2), None
                (xc, aux), _ = jax.lax.scan(lb, (xc, aux), (pp["local"], lo_local))
                nc_local = None
            else:
                (xc, aux), nc_local = jax.lax.scan(
                    local_body, (xc, aux), (pp["local"], lo_local, local_ca))
            xo, nc_glob, a, _ = _attn_mlp_layer(
                cfg, pp["global"], xc,
                lora=None if lo is None else lo["global"],
                lora_scale=lora_scale, positions=positions, window=0,
                cache=None if ca is None else ca["global"],
                decode_position=dpos, moe_impl=moe_impl, block_size=block_size)
            ys = None if ca is None else {"local": nc_local, "global": nc_glob}
            return (xo, aux + a), ys

        pp = params["periods"]
        nper = jax.tree_util.tree_leaves(pp["global"])[0].shape[0]
        lo = lora.get("periods")
        if lo is None:
            lo = _broadcast_none(nper)
        ca = None if cache is None else {"local": cache["local"], "global": cache["global"]}
        if ca is None:
            def pb(c, inp):
                p_, l_ = inp
                (xo, a), _ = period_body(c, (p_, l_, None))
                return (xo, a), None
            (x, aux_total), _ = _scan_layers(pb, (x, aux_total), (pp, lo), remat=remat)
        else:
            (x, aux_total), nc = _scan_layers(period_body, (x, aux_total),
                                              (pp, lo, ca), remat=False)
            new_cache = nc

    elif fam == "moe":
        new_cache = {} if cache is not None else None
        if cfg.first_k_dense:
            x, aux, nc, _ = run_stack(x, params["dense_layers"], lora.get("dense_layers"),
                                      None if cache is None else cache["dense_layers"],
                                      cfg.sliding_window)
            aux_total += aux
            if cache is not None:
                new_cache["dense_layers"] = nc
        x, aux, nc, st = run_stack(x, params["layers"], lora.get("layers"),
                                   None if cache is None else cache["layers"],
                                   cfg.sliding_window)
        aux_total += aux
        if cache is not None:
            new_cache["layers"] = nc
        stats = {"held_rows": st["held_rows"].sum(),
                 "max_expert_rows": st["max_expert_rows"].max()}

    elif fam == "hybrid":
        shared_p = params["shared_attn"]
        shared_lo = lora.get("shared_attn")

        def hperiod_body(carry, inp):
            xc, aux = carry
            pp, lo, ca = inp
            m_ca = None if ca is None else ca["mamba"]

            def mbody(c2, inp2):
                p2, l2, ca2 = inp2
                xo, nc2 = _mamba_layer(cfg, p2, c2, lora=l2, lora_scale=lora_scale,
                                       cache=ca2, decode=decode)
                return xo, nc2

            nm = jax.tree_util.tree_leaves(pp)[0].shape[0]
            lo_m = lo if lo is not None else _broadcast_none(nm)
            if m_ca is None:
                def mb(c2, inp2):
                    p2, l2 = inp2
                    xo, _ = mbody(c2, (p2, l2, None))
                    return xo, None
                xc, _ = jax.lax.scan(mb, xc, (pp, lo_m))
                nc_m = None
            else:
                xc, nc_m = jax.lax.scan(mbody, xc, (pp, lo_m, m_ca))
            xo, nc_a, a, _ = _attn_mlp_layer(
                cfg, shared_p, xc, lora=shared_lo, lora_scale=lora_scale,
                positions=positions, window=0,
                cache=None if ca is None else ca["attn"],
                decode_position=dpos, moe_impl=moe_impl, block_size=block_size)
            ys = None if ca is None else {"mamba": nc_m, "attn": nc_a}
            return (xo, aux + a), ys

        pp = params["mamba_layers"]
        nper = jax.tree_util.tree_leaves(pp)[0].shape[0]
        lo = lora.get("mamba_layers")
        if lo is None:
            lo = _broadcast_none(nper)
        ca = None if cache is None else {"mamba": cache["mamba"], "attn": cache["shared_attn"]}
        if ca is None:
            def hb(c, inp):
                p_, l_ = inp
                (xo, a), _ = hperiod_body(c, (p_, l_, None))
                return (xo, a), None
            (x, aux_total), _ = _scan_layers(hb, (x, aux_total), (pp, lo), remat=remat)
        else:
            (x, aux_total), nc = _scan_layers(hperiod_body, (x, aux_total),
                                              (pp, lo, ca), remat=False)
            new_cache = {"mamba": nc["mamba"], "shared_attn": nc["attn"]}
        if "mamba_trailing" in params:
            tp = params["mamba_trailing"]
            nt = jax.tree_util.tree_leaves(tp)[0].shape[0]
            lo_t = lora.get("mamba_trailing") or _broadcast_none(nt)
            t_ca = None if cache is None else cache["mamba_trailing"]
            if t_ca is None:
                def tb(c, inp):
                    p_, l_ = inp
                    xo, _ = _mamba_layer(cfg, p_, c, lora=l_, lora_scale=lora_scale,
                                         cache=None, decode=decode)
                    return xo, None
                body = jax.checkpoint(tb) if remat else tb
                x, _ = jax.lax.scan(body, x, (tp, lo_t))
            else:
                def tb2(c, inp):
                    p_, l_, ca_ = inp
                    return _mamba_layer(cfg, p_, c, lora=l_, lora_scale=lora_scale,
                                        cache=ca_, decode=decode)
                x, nc_t = jax.lax.scan(tb2, x, (tp, lo_t, t_ca))
                new_cache["mamba_trailing"] = nc_t

    elif fam == "ssm":  # xlstm
        def xperiod_body(carry, inp):
            xc = carry
            pp, lo, ca = inp
            m_ca = None if ca is None else ca["mlstm"]

            def mbody(c2, inp2):
                p2, l2, ca2 = inp2
                return xlstm_mod.mlstm_block(cfg, p2, c2, lora=l2,
                                             lora_scale=lora_scale, cache=ca2,
                                             decode=decode)

            nm = jax.tree_util.tree_leaves(pp["mlstm"])[0].shape[0]
            lo_m = (lo or {}).get("mlstm") if lo is not None else None
            lo_m = lo_m if lo_m is not None else _broadcast_none(nm)
            if m_ca is None:
                def mb(c2, inp2):
                    p2, l2 = inp2
                    xo, _ = mbody(c2, (p2, l2, None))
                    return xo, None
                xc, _ = jax.lax.scan(mb, xc, (pp["mlstm"], lo_m))
                nc_m = None
            else:
                xc, nc_m = jax.lax.scan(mbody, xc, (pp["mlstm"], lo_m, m_ca))
            xo, nc_s = xlstm_mod.slstm_block(
                cfg, pp["slstm"], xc,
                lora=None if lo is None else lo.get("slstm"),
                lora_scale=lora_scale,
                cache=None if ca is None else ca["slstm"], decode=decode)
            ys = None if ca is None else {"mlstm": nc_m, "slstm": nc_s}
            return xo, ys

        pp = params["periods"]
        nper = jax.tree_util.tree_leaves(pp["slstm"])[0].shape[0]
        lo = lora.get("periods") or _broadcast_none(nper)
        ca = None if cache is None else {"mlstm": cache["mlstm"], "slstm": cache["slstm"]}
        if ca is None:
            def xb(c, inp):
                p_, l_ = inp
                xo, _ = xperiod_body(c, (p_, l_, None))
                return xo, None
            x, _ = _scan_layers(xb, x, (pp, lo), remat=remat)
        else:
            x, nc = _scan_layers(xperiod_body, x, (pp, lo, ca), remat=False)
            new_cache = nc
    else:
        raise ValueError(f"forward: unsupported family {fam!r}")

    x = apply_norm(cfg.norm, params["final_norm"], x)
    tied = params["embed"]["embedding"] if cfg.tie_embeddings else None
    logits = unembed(params.get("lm_head", {}), x, tied_embedding=tied,
                     lora=(lora or {}).get("lm_head"), lora_scale=lora_scale)
    return logits, aux_total, new_cache, stats


def _broadcast_none(n: int):
    # scanning over a None pytree: jax treats None as an empty pytree, which is
    # valid as a scan xs — every slice is None.
    return None
