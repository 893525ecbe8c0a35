"""Fused LoRA matmul Pallas kernel:  y = x @ W + scale · (x @ a) @ b.

TPU adaptation of the paper's "LoRA efficiency" argument (DESIGN §2): the
naive formulation launches three GEMMs with an HBM round-trip for the rank-r
intermediate ``x @ a``. Here the intermediate lives in a VMEM scratch pinned
across the K-stream — the adapter path adds ~zero HBM traffic on top of the
base GEMM (r ≤ 64 ≪ the 128-lane tile).

Tiling: grid (M/bm, N/bn, K/bk); x and W stream through VMEM in MXU-aligned
(128-multiple) tiles; f32 accumulation in the output tile; the rank-r ``x@a``
partial accumulates in scratch and is folded in with ``b`` on the last K step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.padding import pad_axis as _pad_axis


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, xa_ref, *, scale: float, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    o_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    xa_ref[...] += jnp.dot(x, a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] += scale * jnp.dot(
            xa_ref[...].astype(b_ref.dtype), b_ref[...],
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret"))
def lora_matmul(x: jnp.ndarray, w: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                *, scale: float = 1.0, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """x: (M, K), w: (K, N), a: (K, r), b: (r, N) → (M, N) f32.

    Tile-indivisible (M, N, K) are zero-padded to the next (bm, bn, bk)
    multiple and the result sliced back — zero K-rows/columns add nothing to
    either the base or the adapter product, so odd model dims (whisper/qwen
    head dims) run the fused path instead of crashing.
    """
    m, kdim = x.shape
    _, n = w.shape
    r = a.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    x = _pad_axis(_pad_axis(x, bm, 0), bk, 1)
    w = _pad_axis(_pad_axis(w, bk, 0), bn, 1)
    a = _pad_axis(a, bk, 0)
    b = _pad_axis(b, bn, 1)
    mp, kp = x.shape
    np_ = w.shape[1]
    nk = kp // bk

    grid = (mp // bm, np_ // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, r), jnp.float32)],
        interpret=interpret,
        name="lora_matmul",
    )(x, w, a, b)[:m, :n]
