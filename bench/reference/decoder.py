"""Plain reference of a Qwen2 / Llama decoder with LoRA, its loss, and three
steps of clipped AdamW: straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``, with no kernels, cache or batching tricks.

It follows the published architecture (pre-norm RMSNorm, rotary embeddings
with the rotate-half convention, grouped-query causal attention with
optional q/k/v bias, SwiGLU MLP, tied or untied output head) and the stated
training recipe (mean token cross-entropy; LoRA ``y += s·(x a) b`` on the
listed projections; global-norm clipping; decoupled AdamW with bias
correction; linear warmup then a constant rate). It imports nothing of the
program: weights arrive as a flat ``{path: array}`` dict, drawn by the
benchmark from the seed; paths name the layer stack
(``layers/attn/q_proj/kernel`` with a leading layer axis, kernels stored
``(d_in, d_out)``).

``mm`` is the one place a matrix product happens, so the control (the same
reference in a lower precision) swaps it alone.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def _quant(t, dtype, top):
    """Round ``t`` to a float8 ``dtype`` under a per-tensor scale that maps
    its largest magnitude to ``top``."""
    t = t.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / top
    return (t / s).astype(dtype).astype(jnp.float32) * s


def _e4m3(t):
    return _quant(t, jnp.float8_e4m3fn, 448.0)


def _e5m2(t):
    return _quant(t, jnp.float8_e5m2, 57344.0)


@jax.custom_vjp
def mm_fp8(x, w):
    """The control, one precision step below bfloat16: float8 training as
    it is usually done. Forward operands in e4m3 and gradients in e5m2, each
    with a per-tensor scale; every product then exact."""
    return jnp.matmul(_e4m3(x), _e4m3(w), precision=HIGHEST)


def _fp8_fwd(x, w):
    qx, qw = _e4m3(x), _e4m3(w)
    return jnp.matmul(qx, qw, precision=HIGHEST), (qx, qw)


def _fp8_bwd(res, g):
    qx, qw = res
    _, vjp = jax.vjp(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), qx, qw)
    return vjp(_e5m2(g))


mm_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, H, D); rotate-half convention."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(x, layer, lora, name, group, scale, mm):
    y = mm(x, layer[f"{group}/{name}/kernel"])
    if f"{group}/{name}/bias" in layer:
        y = y + layer[f"{group}/{name}/bias"].astype(jnp.float32)
    if f"{group}/{name}/a" in lora:
        y = y + scale * mm(mm(x, lora[f"{group}/{name}/a"]),
                           lora[f"{group}/{name}/b"])
    return y


def _layer(cfg, scale, mm, x, layer, lora):
    b, s, _ = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    a_in = _rms(x, layer["attn_norm/scale"], eps)
    q = _proj(a_in, layer, lora, "q_proj", "attn", scale, mm).reshape(b, s, h, hd)
    k = _proj(a_in, layer, lora, "k_proj", "attn", scale, mm).reshape(b, s, kv, hd)
    v = _proj(a_in, layer, lora, "v_proj", "attn", scale, mm).reshape(b, s, kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    scores = mm(qh, kh.transpose(0, 1, 3, 2)) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = mm(jax.nn.softmax(scores, axis=-1), vh)
    att = att.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    x = x + _proj(att, layer, lora, "o_proj", "attn", scale, mm)
    m_in = _rms(x, layer["mlp_norm/scale"], eps)
    gate = _proj(m_in, layer, lora, "gate_proj", "mlp", scale, mm)
    up = _proj(m_in, layer, lora, "up_proj", "mlp", scale, mm)
    return x + _proj(jax.nn.silu(gate) * up, layer, lora, "down_proj", "mlp",
                     scale, mm)


def _split(flat: Dict[str, Any], prefix: str = "layers/"):
    """Stacked per-layer leaves (prefix stripped) and the rest."""
    stack = {k[len(prefix):]: v for k, v in flat.items() if k.startswith(prefix)}
    rest = {k: v for k, v in flat.items() if not k.startswith(prefix)}
    return stack, rest


def loss(cfg, scale, mm, weights, lora, batch):
    """Mean next-token cross-entropy of one batch (all positions count)."""
    wl, wr = _split(weights)
    ll, _ = _split(lora)
    x = wr["embed/embedding"][batch["tokens"]].astype(jnp.float32)

    @jax.checkpoint
    def body(x, xs):
        layer, lo = xs
        return _layer(cfg, scale, mm, x, layer, lo), None

    x, _ = jax.lax.scan(body, x, (wl, ll))
    x = _rms(x, wr["final_norm/scale"], cfg["rms_norm_eps"])
    head = (wr["embed/embedding"].T if cfg["tie_word_embeddings"]
            else wr["lm_head/kernel"])
    logits = mm(x, head)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def lr_at(step: int, recipe: Dict[str, Any]) -> float:
    warm = max(1.0, recipe["warmup_ratio"] * recipe["total_steps"])
    return recipe["lr"] * (step / warm if step < warm else 1.0)


def train_steps(cfg: Dict[str, Any], recipe: Dict[str, Any], weights, lora,
                batches: List[Dict[str, Any]], mm: Callable = mm_f32,
                scale: float = 1.0) -> Dict[str, Any]:
    """Run ``len(batches)`` steps from ``lora`` (flat f32 dict). Returns the
    losses, the first step's gradient as the optimizer gets it (after
    clipping), and the adapters after the last step."""
    b1, b2 = recipe["beta1"], recipe["beta2"]
    eps, wd, clip = recipe["eps"], recipe["weight_decay"], recipe["grad_clip"]

    @jax.jit
    def grad_fn(weights, lora, batch):
        return jax.value_and_grad(
            lambda lo: loss(cfg, scale, mm, weights, lo, batch))(lora)

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

    with jax.default_matmul_precision("highest"):
        m = {k: jnp.zeros_like(x) for k, x in lora.items()}
        v = {k: jnp.zeros_like(x) for k, x in lora.items()}
        losses, first = [], None
        for i, batch in enumerate(batches):
            val, g = grad_fn(weights, lora, batch)
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
            c = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-9))
            g = {k: x * c for k, x in g.items()}
            if first is None:
                first = g
            lora, m, v = update(lora, m, v, g, float(i + 1),
                                lr_at(i + recipe.get("step0", 0), recipe))
            losses.append(float(val))
    return {"losses": losses, "grad1": first, "lora": lora}
