"""Weighted LoRA factor mean Pallas kernel:  x̄ = Σ_c w_c · x_c  over a
stacked client axis.

The round-close engine (core/engine.py) aggregates the global adapter factors
ā = Σ w_c a_c and b̄ = Σ w_c b_c from ``(C_max, …)``-stacked client buffers.
This kernel performs that reduction tile-by-tile with the per-client weight
vector delivered through scalar prefetch (SMEM), so the weights are resident
before the tile loop starts and zero-weight lanes act as a participation
mask — ragged rounds reuse the one compiled program, only the vector changes.

``weights=None`` takes the uniform path: the client sum is unrolled in slot
order and divided by C at the end, mirroring ``core/aggregation.py``'s
``tree_mean`` (``sum(...)/k``) op-for-op, which keeps the uniform path bitwise
identical to the jitted jnp ground truth.

Factors are small relative to W0 (m·r + r·n ≪ m·n) so this is VPU-bound; the
value of fusing it into the round-close program is dispatch count and HBM
re-reads, not FLOPs. Tile-indivisible shapes are zero-padded and sliced back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.padding import pad_axis as _pad_axis


def _kernel(x_ref, o_ref, *, num_clients: int):
    x = x_ref[...].astype(jnp.float32)  # (C, bm, bn)
    acc = x[0]
    for c in range(1, num_clients):  # static unroll: C is small (cross-silo)
        acc = acc + x[c]
    o_ref[...] = acc / num_clients


def _kernel_weighted(w_ref, x_ref, o_ref, *, num_clients: int):
    x = x_ref[...].astype(jnp.float32)  # (C, bm, bn)
    acc = jnp.zeros_like(x[0])
    for c in range(num_clients):
        acc += w_ref[c] * x[c]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def lora_factor_mean(stack: jnp.ndarray, weights: jnp.ndarray | None = None, *,
                     bm: int = 256, bn: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """stack: (C, m, n) → (m, n) f32 weighted mean over the client axis.

    ``weights`` — optional (C,) f32 normalized weight vector (zeros mask
    non-delivered lanes); ``None`` → uniform 1/C mean (slot-order sum, /C).
    """
    c, m, n = stack.shape
    bm, bn = min(bm, m), min(bn, n)
    xp = _pad_axis(_pad_axis(stack, bm, 1), bn, 2)
    mp, np_ = xp.shape[1:]
    grid = (mp // bm, np_ // bn)

    if weights is None:
        return pl.pallas_call(
            functools.partial(_kernel, num_clients=c),
            grid=grid,
            in_specs=[pl.BlockSpec((c, bm, bn), lambda i, j: (0, i, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            interpret=interpret,
            name="factor_mean",
        )(xp)[:m, :n]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[pl.BlockSpec((c, bm, bn), lambda i, j, *_: (0, i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel_weighted, num_clients=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
        name="factor_mean_weighted",
    )(weights.astype(jnp.float32), xp)[:m, :n]
