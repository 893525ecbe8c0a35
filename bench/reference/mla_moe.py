"""Plain reference of a DeepSeek-V2 decoder with LoRA, its loss, and three
steps of clipped AdamW: straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``, with no kernels, cache or batching tricks.

It follows the published layer (arXiv:2405.04434; the Hugging Face
``modeling_deepseek.py`` of ``deepseek-ai/DeepSeek-V2``):

* pre-norm RMSNorm; latent attention: q through ``q_down`` → RMSNorm →
  ``q_up`` (``q_lora_rank``), k/v through ``kv_down`` → RMSNorm → ``k_up`` /
  ``v_up`` (``kv_lora_rank``), plus one decoupled rope key of
  ``qk_rope_head_dim`` shared by all heads;
* rope on interleaved pairs at YaRN frequencies (``rope_scaling``: the
  correction range between ``beta_fast`` and ``beta_slow`` rotations, the
  linear ramp, ``mscale / mscale_all_dim`` on cos and sin), and the softmax
  scale ``(qk_nope + qk_rope)^-0.5 · mscale(factor, mscale_all_dim)²``;
* causal softmax attention; ``first_k_dense_replace`` SwiGLU layers of
  ``intermediate_size``, then routed-expert layers: f32 softmax scores over
  the router's published width, ``group_limited_greedy`` (the top
  ``topk_group`` of ``n_group`` groups by their best expert, then the top
  ``num_experts_per_tok`` inside them), weights times
  ``routed_scaling_factor`` where ``norm_topk_prob`` is false, SwiGLU experts
  of ``moe_intermediate_size``, and ``n_shared_experts`` shared experts as one
  SwiGLU of their summed width;
* an untied output head; mean token cross-entropy; LoRA ``y += s·(x a) b`` on
  the listed projections and, under ``lora_experts``, on each held expert's;
  global-norm clipping, decoupled AdamW with bias correction, linear warmup
  then a constant rate (``decoder.py``'s recipe).

Departures from the published model, each the benchmark's cut or layout:

* the layer holds the ``n_routed_experts`` experts from the offset the file's
  ``"program"`` object gives (``expert_offset``) of the router's
  ``published_n_routed_experts``; what the other experts would add is left
  out, as on the chip of an expert-parallel deployment that holds them;
* the vocabulary is the file's slice (``vocab_size``), for ids and loss;
* ``k_up`` and ``v_up`` are two matrices with an adapter each, where the
  published ``kv_b_proj`` is one matrix of both; the product is the same;
* no auxiliary loss (``seq_aux``): the router is frozen under LoRA and its
  coefficient is not published.

So that it fits beside the bf16 base on one chip, W0 stays in its stored
bfloat16 and each product upcasts it; each layer is recomputed in the
backward; attention is taken in blocks of queries, each against every key;
and each held expert is applied to the tokens routed to it, gathered by
their routing weight (at most four times the mean share per step; a step
whose routing gives an expert more is taken again with every token, so no
token is ever dropped).

Weights arrive as a flat ``{path: array}`` dict drawn by the benchmark from
the seed, in the program's layout (kernels ``(d_in, d_out)``, expert stacks
``(layers, experts, d_in, d_out)``); the reference imports nothing of the
program. ``mm`` is the one place a matrix product happens, so the control
(the same reference in a lower precision) swaps it alone.
"""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"_mla_moe_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_decoder = _sibling("decoder")
mm_f32, mm_fp8, lr_at = _decoder.mm_f32, _decoder.mm_fp8, _decoder.lr_at
_rms, _split = _decoder._rms, _decoder._split

# query rows per attention block
Q_BLOCK = 256
# tokens gathered per held expert and step, over the mean share T·k/E
CAPACITY_OVER_MEAN = 4


# ---------------------------------------------------------------- YaRN rope

def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, base: float, rs: Dict[str, Any]) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies."""
    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / np.float32(base) ** exps
    inter = 1.0 / (np.float32(rs["factor"]) * np.float32(base) ** exps)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope_tables(cfg, s: int):
    """cos, sin (S, d) of the published rope (``cat(freqs, freqs)``)."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling")
    if rs:
        inv = yarn_inv_freq(d, base, rs)
        m = (yarn_get_mscale(rs["factor"], rs["mscale"])
             / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    else:
        inv = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float32) / d)
        m = 1.0
    freqs = np.outer(np.arange(s, dtype=np.float32), inv)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def _rope(x, cos, sin):
    """x (B, S, H, d): de-interleave the pairs, then rotate halves."""
    b, s, h, d = x.shape
    x = x.reshape(b, s, h, d // 2, 2).swapaxes(-1, -2).reshape(b, s, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def softmax_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


# ------------------------------------------------------------------ layers

def _proj(x, layer, lora, name, scale, mm):
    y = mm(x, layer[f"{name}/kernel"])
    if f"{name}/a" in lora:
        y = y + scale * mm(mm(x, lora[f"{name}/a"]), lora[f"{name}/b"])
    return y


def _attention(cfg, mm, q, k, v):
    """Causal softmax attention, q/k (B, S, H, dqk), v (B, S, H, dv), one
    block of queries at a time against every key, the later ones masked."""
    b, s, h, _ = q.shape
    qs, ks, vs = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    scale = softmax_scale(cfg)
    bq = min(Q_BLOCK, s)
    n = s // bq

    @jax.checkpoint
    def block(_, xs):
        qb, start = xs
        sc = mm(qb, ks.transpose(0, 1, 3, 2)) * scale
        rows = start + jnp.arange(bq)[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        return None, mm(jax.nn.softmax(sc, axis=-1), vs)

    qb = qs.reshape(b, h, n, bq, -1).transpose(2, 0, 1, 3, 4)
    _, out = jax.lax.scan(block, None, (qb, jnp.arange(n) * bq))
    return out.transpose(1, 0, 3, 2, 4).reshape(b, s, h, -1)


def _mla(cfg, scale, mm, x, layer, lora, cos, sin):
    b, s, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    qd = _rms(_proj(x, layer, lora, "attn/q_down", scale, mm),
              layer["attn/q_norm/scale"], eps)
    q = _proj(qd, layer, lora, "attn/q_up", scale, mm).reshape(b, s, h,
                                                               nope + rope)
    kv = _proj(x, layer, lora, "attn/kv_down", scale, mm)
    c_kv = _rms(kv[..., :kvr], layer["attn/kv_norm/scale"], eps)
    k_pe = _rope(kv[..., None, kvr:], cos, sin)               # (B, S, 1, rope)
    q_pe = _rope(q[..., nope:], cos, sin)
    k_nope = _proj(c_kv, layer, lora, "attn/k_up", scale, mm).reshape(
        b, s, h, nope)
    v = _proj(c_kv, layer, lora, "attn/v_up", scale, mm).reshape(b, s, h, dv)
    qf = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, h, rope))],
                         axis=-1)
    att = _attention(cfg, mm, qf, kf, v).reshape(b, s, h * dv)
    return _proj(att, layer, lora, "attn/o_proj", scale, mm)


def _swiglu(x, layer, lora, group, scale, mm):
    gate = _proj(x, layer, lora, f"{group}/gate_proj", scale, mm)
    up = _proj(x, layer, lora, f"{group}/up_proj", scale, mm)
    return _proj(jax.nn.silu(gate) * up, layer, lora, f"{group}/down_proj",
                 scale, mm)


def route(cfg, mm, x, router):
    """(T, E) routing weights over the router's published width: zero but
    for each token's chosen experts."""
    t = x.shape[0]
    e = int(cfg.get("published_n_routed_experts") or cfg["n_routed_experts"])
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.softmax(mm(x, router), axis=-1)
    if cfg.get("topk_method", "greedy") == "group_limited_greedy":
        g = cfg["n_group"]
        grouped = scores.reshape(t, g, e // g)
        _, top = jax.lax.top_k(grouped.max(-1), cfg["topk_group"])
        keep = jnp.zeros((t, g)).at[jnp.arange(t)[:, None], top].set(1.0)
        scores = (grouped * keep[..., None]).reshape(t, e)
    w, idx = jax.lax.top_k(scores, k)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * cfg.get("routed_scaling_factor", 1.0)
    return jnp.zeros((t, e)).at[jnp.arange(t)[:, None], idx].add(w)


def _expert_proj(x, layer, lora, name, scale, mm):
    y = mm(x, layer[f"mlp/experts/{name}"])
    if f"mlp/experts/{name}/a" in lora:
        y = y + scale * mm(mm(x, lora[f"mlp/experts/{name}/a"]),
                           lora[f"mlp/experts/{name}/b"])
    return y


def _moe(cfg, scale, mm, x, layer, lora, capacity):
    """The held experts' part of the layer and the shared experts; also the
    most tokens any held expert was routed."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    held = int(cfg["n_routed_experts"])
    off = int(cfg.get("program", {}).get("expert_offset", 0))
    w = route(cfg, mm, xf, layer["mlp/router/kernel"])[:, off:off + held]
    cap = min(capacity, b * s)
    wt, tok = jax.lax.top_k(w.T, cap)                          # (E_held, C)
    xe = xf[tok]                                               # (E_held, C, d)
    gate = _expert_proj(xe, layer, lora, "gate_proj", scale, mm)
    up = _expert_proj(xe, layer, lora, "up_proj", scale, mm)
    ye = _expert_proj(jax.nn.silu(gate) * up, layer, lora, "down_proj", scale,
                      mm)
    y = jnp.zeros_like(xf).at[tok.reshape(-1)].add(
        (wt[..., None] * ye).reshape(-1, d))
    y = y.reshape(b, s, d) + _swiglu(x, layer, lora, "mlp/shared", scale, mm)
    return y, jnp.max(jnp.sum(w > 0, axis=0))


def _layer(cfg, scale, mm, capacity, cos, sin, x, layer, lora):
    eps = cfg["rms_norm_eps"]
    x = x + _mla(cfg, scale, mm, _rms(x, layer["attn_norm/scale"], eps),
                 layer, lora, cos, sin)
    m_in = _rms(x, layer["mlp_norm/scale"], eps)
    if "mlp/router/kernel" in layer:
        y, most = _moe(cfg, scale, mm, m_in, layer, lora, capacity)
        return x + y, most
    return x + _swiglu(m_in, layer, lora, "mlp", scale, mm), jnp.int32(0)


def loss(cfg, scale, mm, weights, lora, batch, capacity):
    """(mean next-token cross-entropy of one batch, the most tokens a held
    expert was routed in any layer)."""
    x = weights["embed/embedding"][batch["tokens"]].astype(jnp.float32)
    cos, sin = _rope_tables(cfg, x.shape[1])
    most = jnp.int32(0)
    for prefix in ("dense_layers/", "layers/"):
        wl, _ = _split(weights, prefix)
        if not wl:
            continue
        ll, _ = _split(lora, prefix)

        @jax.checkpoint
        def body(x, xs):
            layer, lo = xs
            return _layer(cfg, scale, mm, capacity, cos, sin, x, layer, lo)

        x, mosts = jax.lax.scan(body, x, (wl, ll))
        most = jnp.maximum(most, jnp.max(mosts))
    x = _rms(x, weights["final_norm/scale"], cfg["rms_norm_eps"])
    logits = mm(x, weights["lm_head/kernel"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold), most


def train_steps(cfg: Dict[str, Any], recipe: Dict[str, Any], weights, lora,
                batches: List[Dict[str, Any]], mm: Callable = mm_f32,
                scale: float = 1.0) -> Dict[str, Any]:
    """Run ``len(batches)`` steps from ``lora`` (flat f32 dict). Returns the
    losses, the first step's gradient as the optimizer gets it (after
    clipping), and the adapters after the last step."""
    b1, b2 = recipe["beta1"], recipe["beta2"]
    eps, wd, clip = recipe["eps"], recipe["weight_decay"], recipe["grad_clip"]
    routed = int(cfg.get("published_n_routed_experts")
                 or cfg["n_routed_experts"])

    @jax.jit
    def update(lora, m, v, g, t, lr):
        out = {}
        for k in lora:
            m_k = b1 * m[k] + (1 - b1) * g[k]
            v_k = b2 * v[k] + (1 - b2) * g[k] * g[k]
            m_hat = m_k / (1 - b1 ** t)
            v_hat = v_k / (1 - b2 ** t)
            p = lora[k] - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * lora[k])
            out[k] = (p, m_k, v_k)
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    grads = {}

    def grad_fn(capacity):
        if capacity not in grads:
            grads[capacity] = jax.jit(jax.value_and_grad(
                lambda lo, w, b: loss(cfg, scale, mm, w, lo, b, capacity),
                has_aux=True))
        return grads[capacity]

    with jax.default_matmul_precision("highest"):
        m = {k: jnp.zeros_like(x) for k, x in lora.items()}
        v = {k: jnp.zeros_like(x) for k, x in lora.items()}
        losses, first = [], None
        for i, batch in enumerate(batches):
            tokens = int(np.prod(batch["tokens"].shape))
            cap = CAPACITY_OVER_MEAN * -(-tokens * cfg["num_experts_per_tok"]
                                         // routed)
            (val, most), g = grad_fn(cap)(lora, weights, batch)
            if int(most) > cap:          # an expert got more: every token
                (val, most), g = grad_fn(tokens)(lora, weights, batch)
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            c = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
            g = {k: x * c for k, x in g.items()}
            if first is None:
                first = g
            lora, m, v = update(lora, m, v, g, float(i + 1),
                                lr_at(i + recipe.get("step0", 0), recipe))
            losses.append(float(val))
    return {"losses": losses, "grad1": first, "lora": lora}
